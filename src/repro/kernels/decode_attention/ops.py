"""jit'd wrapper: model layout (B, S, KH, hd) caches + position masking."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import decode_attention_flat


@partial(jax.jit, static_argnames=("window", "block_s", "interpret"))
def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     block_s: int = 512, interpret=None):
    """q: (B, 1, H, hd); caches: (B, S, KH, hd); pos scalar or (B,).
    Returns (B, 1, H, hd)."""
    B, _, H, hd = q.shape
    S, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    block_s = min(block_s, max(S, 8))
    pad = (-S) % block_s
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_cache = jnp.pad(k_cache, widths)
        v_cache = jnp.pad(v_cache, widths)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(B * KH, S + pad, hd)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(B * KH, S + pad, hd)
    qf = q.reshape(B, KH, G, hd).reshape(B * KH, G, hd)
    o = decode_attention_flat(qf, kf, vf, jnp.repeat(pos_b, KH),
                              block_s=block_s, kv_len=S, window=window,
                              interpret=interpret)
    return o.reshape(B, 1, H, hd)
