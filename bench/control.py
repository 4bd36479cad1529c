#!/usr/bin/env python3
"""The lower-precision control, and the readings the limits are set from.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5

For each seed, one run of the cell as the benchmark makes it (its own
weights, corpus and window), judged twice by the harness's own
comparison (``harness.check_run``): as the program served it, and with
the control in the program's place, the reference computed one
precision below what the configuration states:

  * generation (bfloat16 stated): the reference with int8 weights and
    int8 activations in every weight product; at each served position
    of the same prompts and served tokens, the token it puts first,
    read against the float32 reference;
  * retrieval (float32 at ``highest`` stated): the int8 reference's
    query embeddings, scanned at ``Precision.HIGH`` (three bf16 passes).

Prints one JSON line per seed with both verdicts and both sets of
numbers, then the largest program reading and the smallest control
reading of each number, and how many runs of each were correct.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    from bench.run import finite, gate

    import jax

    cell = harness.find_cell(harness.load_spec(ROOT), args.workload, ROOT)
    peaks = harness.load_peaks(ROOT)
    why = gate(jax.devices(), peaks, cell["chips"])
    if why:
        print(why, file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    program, control, verdicts = {}, {}, []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        res = harness.run(cell, seed, args.seconds, False, t0,
                          peaks[jax.devices()[0].device_kind], control=True)
        prog = {k: v["value"] for k, v in res["checks"].items()}
        ctrl = {k: v["value"] for k, v in res["control"]["checks"].items()}
        verdicts.append((res["correct"], res["control"]["correct"]))
        print(json.dumps(finite({
            "seed": seed, "correct": res["correct"],
            "control_correct": res["control"]["correct"], "program": prog,
            "control": ctrl, "metrics": res["metrics"],
            "memory_peak_bytes": res["device"]["memory_peak_bytes"]})),
            flush=True)
        for k, v in prog.items():
            program[k] = max(program.get(k, v), v)
        for k, v in ctrl.items():
            control[k] = min(control.get(k, v), v)
    print(json.dumps(finite({
        "program_max": program, "control_min": control,
        "program_correct": sum(p for p, _ in verdicts),
        "control_correct": sum(c for _, c in verdicts),
        "runs": len(verdicts)})), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
