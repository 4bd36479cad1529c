"""Reduction of a JAX profiler trace to the numbers the benchmark reports.

A trace is first flattened into plain lists (``flatten``), so that the
reduction runs the same on a recorded ``.xplane.pb`` and on a small JSON
fixture:

    {"devices": {"<device plane>": [[name, start_ns, dur_ns], ...]},
     "host": [[name, start_ns, dur_ns], ...],
     "program": [[name, start_ns, dur_ns], ...]}

``devices`` holds the operations of each chip's ``XLA Ops`` line, each
named by its HLO instruction and kind (``%topk_sim.1 custom-call``);
``host`` holds the host spans the benchmark records with
``jax.profiler.TraceAnnotation`` (``window``, ``plan``,
``provider.complete``, ``provider.embed``); ``program`` the program's own
spans (``repro.core.telemetry.SPANS``), kept apart so that the
reduction below, which reads ``host`` alone, is not changed by them.
Device and host events share the profiler's clock.

``reduce`` takes the ``window`` span as the traced window and returns:

  * ``busy_s``: the union of the operation intervals inside the window,
    averaged over the chips (overlapping operations count once);
  * ``window_s``: the window's length;
  * ``ops``: total device seconds per operation name, summed over chips;
  * ``idle``: per host label, the idle seconds and gap count, where each
    gap between busy intervals is labelled by the innermost benchmark
    span open at its midpoint (``window`` alone: between plans).
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from collections import defaultdict

SPANS = ("window", "plan", "provider.complete", "provider.embed")
try:
    from repro.core.telemetry import SPANS as PROGRAM_SPANS
except ImportError:             # a program without spans of its own
    PROGRAM_SPANS = ()
OPS_LINE = "XLA Ops"
_KIND = re.compile(r"\b([a-z][a-z0-9-]*)\(")


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), ...`` -> ``%fusion.12 fusion``:
    the instruction's name and kind, without its shapes and operands."""
    name, eq, rhs = hlo.partition(" = ")
    if not eq:
        return hlo
    kind = _KIND.search(rhs)
    return f"{name} {kind.group(1)}" if kind else name


def flatten(profile_dir: str, spans=SPANS) -> dict:
    """Plain device ops, the benchmark's host spans named in ``spans``
    and the program's (``PROGRAM_SPANS``), of the newest ``.xplane.pb``
    under ``profile_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, host, prog, names = {}, [], [], {}
    wanted, ours = set(spans), set(PROGRAM_SPANS)
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    n = names.get(e.name)
                    if n is None:
                        n = names[e.name] = op_name(e.name)
                    ops.append([n, float(e.start_ns), float(e.duration_ns)])
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ours:
                        out = prog
                    elif e.name in wanted:
                        out = host
                    else:
                        continue
                    out.append([e.name, float(e.start_ns),
                                float(e.duration_ns)])
    return {"devices": devices, "host": host, "program": prog}


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def merge(intervals):
    """Sorted, non-overlapping union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def gaps(busy, lo, hi):
    """Idle (start, end) stretches of [lo, hi) not covered by ``busy``
    (merged, clipped)."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


class Labeller:
    """Name of the innermost host span open at a time (the one that
    started last), or ``"none"``."""

    def __init__(self, host):
        self.spans = sorted(host, key=lambda h: h[1])
        self.starts = [h[1] for h in self.spans]

    def __call__(self, t):
        for i in range(bisect.bisect_right(self.starts, t) - 1, -1, -1):
            name, s, d = self.spans[i]
            if t < s + d:
                return name
        return "none"


def window_of(tr: dict):
    spans = [(s, s + d) for name, s, d in tr["host"] if name == "window"]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(tr: dict) -> dict | None:
    """Busy and idle time, op totals and labelled idle gaps inside the
    traced window; None when the trace has no window span."""
    win = window_of(tr)
    if win is None:
        return None
    lo, hi = win
    host = [h for h in tr["host"] if h[0] != "window"] + [
        ["window", lo, hi - lo]]
    ops = defaultdict(float)
    busy_total = 0.0
    idle = defaultdict(lambda: [0.0, 0, 0.0])
    label_at = Labeller(host)
    chips = tr["devices"]
    for events in chips.values():
        for name, s, d in events:
            if s + d > lo and s < hi:
                ops[name] += (min(s + d, hi) - max(s, lo)) * 1e-9
        busy = clip(merge([(s, s + d) for _, s, d in events]), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for s, e in gaps(busy, lo, hi):
            rec = idle[label_at((s + e) / 2)]
            rec[0] += (e - s) * 1e-9 / len(chips)
            rec[1] += 1
            rec[2] = max(rec[2], (e - s) * 1e-9)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total * 1e-9 / len(chips) if chips else 0.0,
        "chips": len(chips),
        "ops": dict(ops),
        "idle": {k: {"seconds": v[0], "gaps": v[1], "longest_s": v[2]}
                 for k, v in idle.items()},
    }


def events_named(tr: dict, needle: str):
    """Device events (over all chips) whose name contains ``needle``."""
    return [e for events in tr["devices"].values() for e in events
            if needle in e[0]]


def breakdown(red: dict, top: int = 10) -> dict:
    """The ``breakdown`` block of a traced result line."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle"].items(), key=lambda kv: -kv[1]["seconds"])
    return {
        "device_ops": [[name, s] for name, s in ops],
        "idle_gaps": [[f"{name}: {v['gaps']} gaps, longest "
                       f"{v['longest_s']} s", v["seconds"]]
                      for name, v in idle[:top]],
    }
