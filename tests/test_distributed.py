"""Distribution substrate tests that need >1 device: run in a subprocess
with 8 host-platform devices (the 512-device override is dryrun-only).

Covers:
  * elastic re-shard: checkpoint saved under mesh (2,4) restores and keeps
    training under mesh (4,2) with identical loss trajectory;
  * sharded corpus top-k: numerics match the single-device oracle and the
    compiled HLO keeps the corpus sharded (no full all-gather of it);
    a ``VectorIndex`` over the mesh places one padded shard per device.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

ELASTIC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.models import sharding as S
    from repro.training import HParams, adamw_init, make_train_step, opt_specs
    from repro.training.checkpoint import CheckpointManager
    from repro.training.data import DataConfig, SyntheticTokenPipeline

    # granite: rmsnorm everywhere, so the checkpoint tree has no empty
    # subtrees (olmo's non-parametric LN has {} params, which npz drops)
    cfg = get_smoke_config("granite-8b").replace(remat=False,
                                                 shard_multiple=4)
    hp = HParams(lr=1e-3, warmup_steps=1, total_steps=10)
    data = SyntheticTokenPipeline(DataConfig(cfg.vocab_size, 16, 8))

    def build(mesh):
        policy = S.MeshPolicy(mesh, cfg, 8)
        pspecs = S.param_specs(cfg, mesh)
        sds = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
        ospecs = opt_specs(pspecs, sds, mesh)
        bspecs = S.batch_specs(cfg, mesh, 8, "train")
        psh = S.to_shardings(mesh, pspecs)
        osh = S.to_shardings(mesh, ospecs)
        step = jax.jit(make_train_step(cfg, hp, policy),
                       in_shardings=(psh, osh,
                                     S.to_shardings(mesh, bspecs)),
                       out_shardings=(psh, osh, None))
        return step, pspecs, ospecs

    def put(tree, mesh, specs):
        return jax.tree.map(
            lambda a, s: jax.device_put(
                jnp.asarray(a), jax.sharding.NamedSharding(mesh, s)),
            tree, specs, is_leaf=lambda x: not isinstance(x, (dict, list)))

    losses = {}
    # reference: uninterrupted run on mesh A
    mesh_a = make_mesh((2, 4), ("data", "model"))
    step_a, pspecs_a, ospecs_a = build(mesh_a)
    params = put(M.init_params(cfg, jax.random.PRNGKey(0)), mesh_a, pspecs_a)
    opt = put(adamw_init(params), mesh_a, ospecs_a)
    ref = []
    for i in range(6):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        params, opt, m = step_a(params, opt, batch)
        ref.append(float(m["loss"]))
    losses["ref"] = ref

    # elastic: 3 steps on mesh A -> checkpoint -> restore on mesh B (4,2)
    params = put(M.init_params(cfg, jax.random.PRNGKey(0)), mesh_a, pspecs_a)
    opt = put(adamw_init(params), mesh_a, ospecs_a)
    for i in range(3):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        params, opt, m = step_a(params, opt, batch)
    mgr = CheckpointManager("/tmp/elastic_ck", keep=1)
    mgr.save(3, {"params": params, "opt": opt})

    mesh_b = make_mesh((4, 2), ("data", "model"))
    step_b, pspecs_b, ospecs_b = build(mesh_b)
    state = mgr.restore_latest()
    params_b = put(state["params"], mesh_b, pspecs_b)
    opt_b = put(state["opt"], mesh_b, ospecs_b)
    cont = []
    for i in range(3, 6):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        params_b, opt_b, m = step_b(params_b, opt_b, batch)
        cont.append(float(m["loss"]))
    losses["elastic"] = cont
    print(json.dumps(losses))
""")

SHARDED_TOPK = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    sys.path.insert(0, "src")
    import numpy as np
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.retrieval.distributed import make_sharded_topk
    from repro.kernels.topk_sim.ref import topk_sim_ref

    mesh = make_mesh((8,), ("data",))
    rng = np.random.default_rng(0)
    corpus = jnp.asarray(rng.standard_normal((4096, 32)), jnp.float32)
    queries = jnp.asarray(rng.standard_normal((5, 32)), jnp.float32)
    fn = make_sharded_topk(mesh, k=10)
    lowered = fn.lower(corpus, queries)
    txt = lowered.compile().as_text()
    s, i = fn(corpus, queries)
    s_ref, i_ref = topk_sim_ref(corpus, queries, 10)
    ok_scores = bool(np.allclose(np.asarray(s), np.asarray(s_ref),
                                 atol=1e-5))
    ok_idx = bool((np.asarray(i) == np.asarray(i_ref)).all())
    # the corpus itself must stay sharded: no 4096x32 f32 all-gather
    corpus_gathered = "f32[4096,32]{1,0} all-gather" in txt

    # VectorIndex over a mesh: 4100 rows pad to 4104 = 8 x 513, each
    # shard placed on its own device, padding rows never returned
    from repro.retrieval import VectorIndex
    vecs = rng.standard_normal((4100, 32)).astype(np.float32)
    index = VectorIndex(vecs, mesh=mesh)
    si, ii = index.topk(np.asarray(queries), 10)
    s_ref2, i_ref2 = topk_sim_ref(jnp.asarray(vecs), queries, 10)
    shards = index.device_corpus(mesh).addressable_shards
    print(json.dumps({"scores": ok_scores, "idx": ok_idx,
                      "corpus_gathered": corpus_gathered,
                      "index_idx": bool((ii == np.asarray(i_ref2)).all()),
                      "index_scores": bool(np.allclose(
                          si, np.asarray(s_ref2), atol=1e-5)),
                      "shard_rows": sorted({sh.data.shape[0]
                                            for sh in shards}),
                      "shard_devices": len({sh.device for sh in shards})}))
""")


def _run(script, timeout=900):
    out = subprocess.run([sys.executable, "-c", script], cwd=ROOT,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_elastic_reshard_continues_training():
    losses = _run(ELASTIC)
    import numpy as np
    # continuing on a different mesh reproduces the reference trajectory;
    # 1e-3 rel: the (4,2) mesh reduces in a different order than (2,4), so
    # bf16 matmul accumulation drifts a few e-4 per step (seed rtol=2e-4
    # flaked at 2.5e-4)
    np.testing.assert_allclose(losses["elastic"], losses["ref"][3:],
                               rtol=1e-3, atol=1e-3)


@pytest.mark.slow
def test_sharded_topk_matches_oracle_and_stays_sharded():
    rec = _run(SHARDED_TOPK)
    assert rec["scores"] and rec["idx"]
    assert not rec["corpus_gathered"], "corpus was all-gathered"
    assert rec["index_idx"] and rec["index_scores"]
    assert rec["shard_rows"] == [513] and rec["shard_devices"] == 8
