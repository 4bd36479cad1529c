"""The program's own spans and counters, on the profiler's clock.

``span(name)`` marks a stretch of host work.  It enters a
``jax.profiler.TraceAnnotation(name)`` once JAX is loaded, so that a
profiler trace shows the span on the host timeline, on the same clock as
the device's operations, and it adds the span's count and
``time.perf_counter`` seconds to a process-wide aggregate keyed by name.
``count(name, n)`` adds to a counter in the same aggregate.
``snapshot()`` returns ``{name: {"n": int, "s": float}}``; it only grows
over the life of the process, so a reader takes the difference of two
snapshots.

A span opened inside an open span of the same name on the same thread is
not a second span: its time is already counted.

``install()`` (idempotent; ``LocalJaxProvider`` calls it) adds the JAX
runtime's own events: every backend compilation counts as
``compile.<fun_name>``, the name of the jitted function, and every Python
garbage collection is a ``python.gc`` span.

``SPANS`` names every span the program opens; ``WAITS`` those among them
that wait for another thread rather than work.
"""

from __future__ import annotations

import gc
import sys
import threading
import time

SPANS = (
    "pipeline.build",         # Pipeline methods that add a plan node
    "pipeline.optimize",      # collect(): planning up to the first node
    "scheduler.dispatch",     # one batch request on a scheduler worker
    "provider.engine_wait",   # waiting for the local provider's engine
    "engine.admit",           # ServingEngine.step: waiting -> slots
    "engine.prefill",         # one prefill chunk and its cache merge
    "engine.decode",          # the decode step's dispatch
    "engine.sample",          # argmax, device-to-host copy, bookkeeping
    "engine.embed",           # one embedding batch
    "retrieval.fingerprint",  # corpus_fingerprint
    "retrieval.index",        # corpus selection and index lookup
    "retrieval.join",         # candidates into rows
    "python.gc",              # a garbage collection (after install())
)
WAITS = ("provider.engine_wait",)
# Counters are free-form (``count(name, n)``); the program's are:
#   engine.slot_steps            active slots, added once per decode step
#   retrieval.fingerprint_reuse  a Table.text_fingerprint memo hit, where
#                                a retrieval.fingerprint span would be
#   compile.<function>           a backend compilation (after install())

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
_agg: dict = {}
# python.gc is kept apart from _agg: a collection can start on a thread
# that holds _lock, so the callback must neither take it nor resize _agg
_gc = [0, 0.0]
_gc_open = [0.0, None]          # start time, annotation
_annotate = None                # jax.profiler.TraceAnnotation, once loaded
_installed = False
_now = time.perf_counter


class _Open(threading.local):
    def __init__(self):
        self.names = set()


_open = _Open()


def _annotation():
    """``TraceAnnotation`` once JAX has been imported (by anyone), else
    None: importing the program's core never imports JAX."""
    global _annotate
    if _annotate is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation
        _annotate = TraceAnnotation
    return _annotate


def _add(name: str, n: int, s: float):
    with _lock:
        rec = _agg.get(name)
        if rec is None:
            rec = _agg[name] = [0, 0.0]
        rec[0] += n
        rec[1] += s


class _Span:
    __slots__ = ("name", "names", "t0", "ann")

    def __init__(self, name: str):
        self.name = name
        self.t0 = None
        self.ann = None

    def __enter__(self):
        self.names = names = _open.names
        if self.name in names:
            return self
        names.add(self.name)
        ann = _annotate or _annotation()
        if ann is not None:
            self.ann = ann(self.name)
            self.ann.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, *exc):
        if self.t0 is None:
            return False
        dt = _now() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.names.discard(self.name)
        _add(self.name, 1, dt)
        return False


def span(name: str) -> _Span:
    """Context manager: one ``name`` span around its body."""
    return _Span(name)


def count(name: str, n: int = 1):
    """Add ``n`` to the counter ``name``."""
    _add(name, n, 0.0)


def snapshot() -> dict:
    """``{name: {"n": count, "s": seconds}}`` since the process started."""
    with _lock:
        out = {k: {"n": n, "s": s} for k, (n, s) in _agg.items()}
    n, s = _gc
    if n:
        out["python.gc"] = {"n": n, "s": s}
    return out


def _on_duration(event: str, duration: float, **kw):
    if event == COMPILE_EVENT:
        name = str(kw.get("fun_name", "unknown"))    # "jit(<function>)"
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        count(f"compile.{name}")


def _on_gc(phase: str, info: dict):
    if phase == "start":
        ann = _annotate
        _gc_open[1] = ann("python.gc") if ann is not None else None
        if _gc_open[1] is not None:
            _gc_open[1].__enter__()
        _gc_open[0] = _now()
        return
    dt = _now() - _gc_open[0]
    if _gc_open[1] is not None:
        _gc_open[1].__exit__(None, None, None)
        _gc_open[1] = None
    _gc[0] += 1
    _gc[1] += dt


def install():
    """Count the JAX runtime's compilations and time Python's garbage
    collections from now on (once per process)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax

    _annotation()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    gc.callbacks.append(_on_gc)
