"""Model FLOP/s utilisation of the whole served path, in percent: the
forward FLOPs of the tokens the engine really processed (every prompt
and fed-back token of each finished generation request, the logits of
each served token, and every byte of each text embedded) over the
window's length times the chip's bf16 peak.  Padding, prefill logits
that are thrown away, and retried work that never ran do not count.
The FLOPs are counted by the model block's architecture
(``bench/flops.py``), with the experts this chip holds where the
configuration holds a share of them."""

from bench import flops


def read(rec):
    peak = rec["peak"].get("bf16_flops_per_s")
    if not peak or rec["window_s"] <= 0:
        return None
    m, published = rec["config"]["model"], rec["config"].get("published", {})
    work = (flops.generation_flops(m, rec["sequences"], published)
            + flops.embed_flops(m, rec["embed_lengths"], published))
    if not work:
        return None
    return 100.0 * work / (rec["window_s"] * peak)
