"""jit'd wrapper with padding for the selective-scan kernel."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import ssm_scan_flat


@partial(jax.jit, static_argnames=("chunk", "block_d", "interpret"))
def ssm_scan(x, dt, Bm, Cm, A_log, D, *, chunk: int = 128,
             block_d: int = 256, interpret=None):
    B, S, di = x.shape
    chunk = min(chunk, max(S, 8))
    block_d = min(block_d, di)
    pad_s = (-S) % chunk
    pad_d = (-di) % block_d
    if pad_s or pad_d:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, pad_d)))
        dt = jnp.pad(dt, ((0, 0), (0, pad_s), (0, pad_d)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad_s), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad_s), (0, 0)))
        A_log = jnp.pad(A_log, ((0, pad_d), (0, 0)))
        D = jnp.pad(D, (0, pad_d))
    y = ssm_scan_flat(x, dt, Bm, Cm, A_log, D, chunk=chunk,
                      block_d=block_d, interpret=interpret)
    return y[:, :S, :di]
