"""bench/run.py refuses, with a non-zero exit and no result line, what
it must not measure."""

import json
import os
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench import harness
from bench import run as R

ROOT = str(harness.ROOT)
ARGS = ["--workload", "olmo-1b.batch_map", "--seed", str(2**31 + 3),
        "--seconds", "1", "--trace", "0"]


def has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def run_cli(cwd, root=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, os.path.join(root, "bench",
                                                        "run.py")] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def test_cpu_platform_is_refused():
    p = run_cli(ROOT)
    assert p.returncode != 0
    assert "not 'tpu'" in p.stderr
    assert not has_result(p.stdout)


def test_checkout_without_the_program_is_refused(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for d in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(ROOT, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cli(str(tmp_path), str(tmp_path))
    assert p.returncode != 0 and not has_result(p.stdout)


def dev(platform="tpu", kind="TPU v5 lite"):
    return SimpleNamespace(platform=platform, device_kind=kind)


PEAKS = harness.load_peaks()


@pytest.mark.parametrize("devices,chips,why", [
    ([dev("cpu", "cpu")], 1, "not 'tpu'"),
    ([dev(kind="TPU v9 imaginary")], 1, "no peaks"),
    ([dev()], 4, "needs 4 chips"),
])
def test_gate_refuses(devices, chips, why):
    assert why in R.gate(devices, PEAKS, chips)


def test_gate_admits_a_known_chip():
    assert R.gate([dev()], PEAKS, 1) is None
    assert R.gate([dev()] * 4, PEAKS, 4) is None


def test_unknown_device_kind_exits_nonzero_without_a_result(monkeypatch,
                                                            capsys):
    import jax

    monkeypatch.setattr(jax, "devices",
                        lambda *a: [dev(kind="TPU v9 imaginary")])
    assert R.main(ARGS) != 0
    out = capsys.readouterr()
    assert "no peaks" in out.err and not has_result(out.out)


def test_peaks_cite_their_source():
    for kind, p in PEAKS.items():
        assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == \
            819e9 and p["hbm_bytes"] == 16 * 2**30
        assert "Google Cloud" in p["source"]
