"""chip_smoke.py on the CPU: it refuses to report a result without a TPU,
and each of its phases passes at a small size on the smoke model (the
scan phase up to its compiled-kernel check, which only a chip passes)."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def provider():
    from repro.core.provider import LocalJaxProvider
    return LocalJaxProvider("olmo-1b", seed=0)


def test_main_refuses_a_cpu_platform(smoke, capsys, monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert smoke.main([]) != 0
    out = capsys.readouterr().out.strip().splitlines()
    assert out[-1].startswith("devices: platform=cpu")
    assert not any(line.startswith("{") for line in out)


def test_semantic_and_retrieval_phases_on_smoke_model(smoke, provider,
                                                      capsys):
    smoke.phase_semantic(provider, 0, n_rows=12)
    smoke.phase_retrieval_plan(provider, n_passages=16)
    out = capsys.readouterr().out
    assert "all reached the engine" in out
    assert "hybrid_topk -> llm_rerank: 10 rows" in out
    # the recording wrappers are gone again
    assert "submit" not in vars(provider.engine)


def test_model_phase_on_smoke_model(smoke, provider, capsys):
    smoke.phase_model(provider, 0, n_tokens=40)
    assert "max |logit - f32 reference|" in capsys.readouterr().out


def test_scan_phase_checks_numerics_then_refuses_interpret(smoke, capsys):
    with pytest.raises(smoke.SmokeFailure, match="interpret"):
        smoke.phase_scan(0, n=4096, d=64)
    assert "index sets equal for 8 queries" in capsys.readouterr().out


def test_sharded_phase_on_four_host_devices():
    script = (
        "import sys; sys.path.insert(0, '.');"
        "import chip_smoke as C; C.phase_sharded(0, 4, n=4096, d=32);"
        "print('DONE')")
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "each chip holds a quarter" in out.stdout
    assert "index sets equal for 8 queries" in out.stdout
    assert out.stdout.strip().endswith("DONE")


def test_result_line_shape(smoke, monkeypatch, capsys, tmp_path):
    """The last line is exactly the JSON result (checked with a stand-in
    device, since only a chip reaches it)."""
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    ran = []
    monkeypatch.setattr(smoke, "phase_sharded",
                        lambda seed, chips: ran.append(chips))
    monkeypatch.setattr(jax, "devices", lambda: [Dev()])
    assert smoke.main(["--chips", "4"]) != 0        # one device, not four
    assert ran == []
    monkeypatch.setattr(jax, "devices", lambda: [Dev()] * 4)
    assert smoke.main(["--chips", "4"]) == 0 and ran == [4]
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}
