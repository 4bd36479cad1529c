"""jit'd public wrapper: (B, S, H, hd) layout, padding, GQA flattening."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import flash_attention_bhsd


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret=None):
    """q: (B, Sq, H, hd); k, v: (B, Sk, KH, hd) -> (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Sk, 8))
    qp = _pad_to(q, 1, block_q)
    kp = _pad_to(k, 1, block_k)
    vp = _pad_to(v, 1, block_k)
    qf = qp.transpose(0, 2, 1, 3).reshape(B * H, qp.shape[1], hd)
    kf = kp.transpose(0, 2, 1, 3).reshape(B * KH, kp.shape[1], hd)
    vf = vp.transpose(0, 2, 1, 3).reshape(B * KH, vp.shape[1], hd)
    # flattened (B*H) rows must map to (B*KH) rows by integer division:
    # reorder q rows so heads of one group are adjacent: (B, KH, G) order.
    # q is (B, H) = (B, KH*G) flattened -> already groups G adjacent ✓
    o = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                             block_q=block_q, block_k=block_k, kv_len=Sk,
                             interpret=interpret)
    o = o.reshape(B, H, qp.shape[1], hd).transpose(0, 2, 1, 3)
    return o[:, :Sq]
