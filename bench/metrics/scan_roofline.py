"""Share of its roofline that the retrieval scan's kernel reaches, in
percent: the least time the chip needs for the scans of the window (the
larger of their FLOPs over the bf16 peak and the corpus and query bytes
over the HBM bandwidth; the bytes bound it) over the device time of the
kernel's events in the trace.  The kernel is ``topk_sim``'s block-max
Pallas kernel: a ``custom-call`` whose instruction carries the name of
the jitted ``topk_sim``.  Its corpus renormalisation and rescoring are
separate operations and not counted here."""

from bench import flops
from bench import trace as T

KERNEL = "topk_sim"


def read(rec):
    if rec["trace"] is None or not rec["scans"] or not rec["scan_shape"]:
        return None
    events = [e for e in T.events_named(rec["trace_events"], KERNEL)
              if e[0].endswith(" custom-call")]
    busy = sum(e[2] for e in events) * 1e-9
    if not busy:
        return None
    n, d, q = rec["scan_shape"]
    peak = rec["peak"]
    least = rec["scans"] * max(
        flops.scan_flops(n, d, q) / peak["bf16_flops_per_s"],
        flops.scan_bytes(n, d, q) / peak["hbm_bytes_per_s"])
    return 100.0 * least / busy
