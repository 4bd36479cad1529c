#!/usr/bin/env python3
"""The benchmark's command: one run of one cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout and found as files under
``bench/`` (see ``bench/harness.py``).  The run refuses, with a non-zero
exit and no result, any platform but ``tpu``, a ``device_kind`` missing
from ``bench/peaks.json``, fewer chips than the cell asks for, and a
checkout without the program under ``src/``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: every number the
correctness check compared, beside its limit.  The same numbers are the
last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def refuse(msg: str) -> int:
    print(msg, file=sys.stderr, flush=True)
    return 1


def finite(x):
    """JSON has no NaN or infinity: a non-finite number becomes null."""
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    return x


def gate(devices, peaks: dict, chips: int) -> str | None:
    """Why this machine may not run the cell, or None."""
    dev = devices[0]
    if dev.platform != "tpu":
        return f"platform is {dev.platform!r}, not 'tpu'"
    if dev.device_kind not in peaks:
        return (f"device kind {dev.device_kind!r} has no peaks in "
                f"bench/peaks.json")
    if len(devices) < chips:
        return f"the cell needs {chips} chips, JAX sees {len(devices)}"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        return refuse(f"no program under {ROOT / 'src'}")
    # the checkout's root, not bench/ (whose trace.py would shadow the
    # standard library's), then the program
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    spec = harness.load_spec(ROOT)
    cell = harness.find_cell(spec, args.workload, ROOT)
    peaks = harness.load_peaks(ROOT)

    import jax

    devices = jax.devices()
    why = gate(devices, peaks, cell["chips"])
    if why:
        return refuse(why)
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    print(f"{args.workload}: seed {args.seed}, {args.seconds} s, trace "
          f"{args.trace}; {devices[0].device_kind} x {len(devices)}; "
          f"compile cache {cache_dir}", flush=True)

    result = finite(harness.run(cell, args.seed, args.seconds,
                                bool(args.trace), T_START,
                                peaks[devices[0].device_kind]))
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
