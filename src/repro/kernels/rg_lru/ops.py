"""jit'd wrapper with padding for the RG-LRU recurrence kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import rg_lru_flat


@partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def rg_lru(a, b, *, chunk: int = 128, block_d: int = 512,
           interpret=None):
    """Diagonal recurrence h_t = a_t*h_{t-1} + b_t; a, b: (B, S, di).

    Padding uses a=1, b=0 (identity elements) so padded steps are no-ops.
    """
    B, S, di = a.shape
    chunk = min(chunk, max(S, 8))
    block_d = min(block_d, di)
    pad_s = (-S) % chunk
    pad_d = (-di) % block_d
    if pad_s or pad_d:
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_d)),
                    constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad_s), (0, pad_d)))
    y = rg_lru_flat(a, b, chunk=chunk, block_d=block_d, interpret=interpret)
    return y[:, :S, :di]
