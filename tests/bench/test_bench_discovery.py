"""A configuration, a traffic mix and a per-layer metric are added as new
files and one ``workloads`` entry, and the harness finds each by name
with no edit to any file that was there."""

import hashlib
import json
import shutil
import time

import pytest

from bench import flops, harness

from bench_smoke import smoke_config


def digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()
                                                     ).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path):
    src = harness.ROOT
    shutil.copytree(src / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(tmp_path / "bench")

    conf = json.loads((src / "bench/configs/olmo-1b.json").read_text())
    conf = smoke_config(conf)
    conf.update(name="olmo-small", max_context=1024, smoke=True)
    conf["limits"] = {k: 10.0 for k in conf["limits"]}
    (tmp_path / "bench/configs/olmo-small.json").write_text(json.dumps(conf))
    traffic = json.loads(
        (src / "bench/traffic/batch_map_32.json").read_text())
    traffic.update(rows_per_plan=2, row_bytes={
        "dist": "uniform", "min": 30, "max": 50})
    (tmp_path / "bench/traffic/two_rows.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench/metrics/rows_seen.py").write_text(
        "def read(rec):\n    return rec['rows']\n")

    spec = json.loads((src / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "olmo-small", "source": "test",
                            "file": "bench/configs/olmo-small.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "olmo-small.two_rows",
                              "config": "olmo-small",
                              "traffic": "two_rows", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "rows_seen", "unit": "rows",
                              "better": "higher",
                              "source": "program_counter", "layer": "test",
                              "moves": "rows_per_s",
                              "workloads": ["olmo-small.two_rows"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.find_cell(harness.load_spec(tmp_path),
                             "olmo-small.two_rows", tmp_path)
    assert cell["config"]["name"] == "olmo-small"
    assert cell["traffic"]["rows_per_plan"] == 2
    assert "rows_seen" in [m["name"] for m in cell["per_layer"]]

    res = harness.run(cell, 2**31 + 5, 0.05, True, time.perf_counter(),
                      harness.load_peaks(tmp_path))
    assert res["correct"], res["checks"]
    assert res["metrics"]["rows_seen"] == {"value": 2.0 * res["attempted"],
                                           "unit": "rows"}
    after = digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/olmo-small.json", "traffic/two_rows.json",
        "metrics/rows_seen.py"}


# a latent-attention, routed-expert block, cut by its own file to smoke
# width; the program has no such architecture yet, so the dense smoke
# keys come from its DeepSeek-MoE preset
MOONLIGHT_CUT = {
    "model": {"kv_lora_rank": 32, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16,
              "moe_intermediate_size": 32, "n_routed_experts": 4,
              "n_shared_experts": 1, "num_experts_per_tok": 2,
              "first_k_dense_replace": 1},
    "published": {"n_routed_experts": 8}}


def moonlight_conf(smoke_cut=None) -> dict:
    block = json.loads((harness.ROOT / "tests/bench/data/"
                        "moonlight_block.json").read_text())
    conf = {"name": "moonlight-smoke", "arch": "deepseek-moe-16b",
            "reference": "recorder", "max_context": 1024,
            "model": block["model"], "published": block["published"],
            "limits": {"logit_gap": 0.1}}
    if smoke_cut is not None:
        conf["smoke_cut"] = smoke_cut
    return conf


def test_a_block_that_is_not_dense_brings_its_own_cut(tmp_path):
    src = harness.ROOT
    shutil.copytree(src / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(tmp_path / "bench")

    (tmp_path / "bench/configs/moonlight-smoke.json").write_text(
        json.dumps(moonlight_conf(MOONLIGHT_CUT)))
    (tmp_path / "bench/reference/recorder.py").write_text(
        "class Received:\n"
        "    def __init__(self, *args):\n"
        "        self.args = args\n\n\n"
        "def build(model, seed, published):\n"
        "    return Received(model, seed, published)\n")
    spec = json.loads((src / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "moonlight-smoke", "source": "test",
                            "file": "bench/configs/moonlight-smoke.json",
                            "reduced": ["n_routed_experts"], "why": "test"})
    spec["workloads"].append({"name": "moonlight-smoke.batch_map",
                              "config": "moonlight-smoke",
                              "traffic": "batch_map_32", "chips": 1,
                              "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.find_cell(harness.load_spec(tmp_path),
                             "moonlight-smoke.batch_map", tmp_path)
    conf = smoke_config(cell["config"])
    m = conf["model"]
    assert (m["hidden_size"], m["num_hidden_layers"], m["vocab_size"]) \
        == (64, 2, 256)
    assert "head_dim" not in m
    assert {k: m[k] for k in MOONLIGHT_CUT["model"]} \
        == MOONLIGHT_CUT["model"]
    assert conf["published"] == {"n_routed_experts": 8}
    assert flops.check(m) is m
    assert flops.params(m, conf["published"]) > 0

    seed = 2**31 + 7
    ref = harness.reference_model(conf, seed, tmp_path)
    assert ref.args == (m, seed % 2**32, {"n_routed_experts": 8})

    after = digest(tmp_path / "bench")
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {"configs/moonlight-smoke.json",
                                        "reference/recorder.py"}


@pytest.mark.parametrize("cut,named", [
    (None, "model.kv_lora_rank"),
    ({"model": MOONLIGHT_CUT["model"]}, "published.n_routed_experts"),
])
def test_a_width_left_at_published_size_is_refused(cut, named):
    with pytest.raises(ValueError, match=named):
        smoke_config(moonlight_conf(cut))


@pytest.mark.parametrize("name", ["olmo-1b", "phi-3-vision-4.2b"])
def test_a_dense_block_is_cut_by_the_preset_alone(name):
    from repro.configs import get_smoke_config

    conf = json.loads((harness.ROOT / f"bench/configs/{name}.json"
                       ).read_text())
    c = get_smoke_config(conf["arch"])
    got = smoke_config(conf)
    assert got["model"] == dict(
        conf["model"], num_hidden_layers=c.num_layers, hidden_size=c.d_model,
        num_attention_heads=c.num_heads, num_key_value_heads=c.num_kv_heads,
        head_dim=c.resolved_head_dim, intermediate_size=c.d_ff,
        vocab_size=c.vocab_size)
    assert got.get("published") == conf.get("published")
