"""RG-LRU (Griffin gated linear recurrence) Pallas TPU kernel.

Generic diagonal recurrence h_t = a_t * h_t-1 + b_t over the channel dim,
with gates a, b precomputed by XLA (the block-diagonal gate matmuls are
MXU-friendly einsums; the *recurrence* is the memory-bound part worth a
kernel).  Same chunked-carry structure as ssm_scan: grid
(B, n_channel_blocks, n_time_chunks), carry (block_d,) in VMEM scratch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

F32 = jnp.float32


def _lru_kernel(a_ref, b_ref, o_ref, h_scr, *, chunk: int):
    ck = pl.program_id(2)

    @pl.when(ck == 0)
    def _init():
        h_scr[...] = jnp.zeros(h_scr.shape, F32)

    a = a_ref[0].astype(F32)                # (chunk, bd)
    b = b_ref[0].astype(F32)

    def step(t, carry):
        h, y = carry
        h = a[t] * h + b[t]
        return h, y.at[t].set(h)

    y0 = jnp.zeros(a.shape, F32)
    h, y = jax.lax.fori_loop(0, chunk, step, (h_scr[...], y0))
    h_scr[...] = h
    o_ref[0] = y.astype(o_ref.dtype)


def rg_lru_flat(a, b, *, chunk: int = 128, block_d: int = 512,
                interpret=None):
    """a, b: (B, S, di) -> h: (B, S, di); S % chunk == 0, di % block_d == 0."""
    B, S, di = a.shape
    kernel = functools.partial(_lru_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, di // block_d, S // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b_, d, c: (b_, c, d)),
            pl.BlockSpec((1, chunk, block_d), lambda b_, d, c: (b_, c, d)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d),
                               lambda b_, d, c: (b_, c, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, di), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_d,), F32)],
        interpret=resolve_interpret(interpret),
    )(a, b)
