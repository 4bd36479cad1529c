"""Mamba-1 selective-scan Pallas TPU kernel.

The CUDA selective-scan kernel fights for occupancy with a parallel
Blelloch scan across thread blocks.  TPU adaptation: the grid's sequential
last dimension gives a free cross-chunk carry, so the layout is

   grid (B, n_channel_blocks, n_time_chunks)

with the recurrent state h (block_d, N) living in VMEM scratch across time
chunks.  Within a chunk the recurrence runs as a fori_loop of VPU
elementwise ops over (block_d, N) registersful — the discretised Ā, B̄u
tensors are built in VMEM, never in HBM, which is the entire point: HBM
traffic is just x/dt/B/C/y streaming (the memory-roofline floor), instead
of the (S, d, N) materialisation a naive jnp implementation writes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

F32 = jnp.float32


def _ssm_kernel(x_ref, dt_ref, b_ref, c_ref, alog_ref, d_ref, o_ref, h_scr,
                *, chunk: int):
    ck = pl.program_id(2)

    @pl.when(ck == 0)
    def _init():
        h_scr[...] = jnp.zeros(h_scr.shape, F32)

    x = x_ref[0].astype(F32)                   # (chunk, bd)
    dt = dt_ref[0].astype(F32)                 # (chunk, bd)
    Bm = b_ref[0].astype(F32)                  # (chunk, N)
    Cm = c_ref[0].astype(F32)                  # (chunk, N)
    A = -jnp.exp(alog_ref[...].astype(F32))    # (bd, N)
    D = d_ref[...].astype(F32)                 # (bd,)

    a = jnp.exp(dt[:, :, None] * A[None])      # (chunk, bd, N) in VMEM only
    bu = (dt * x)[:, :, None] * Bm[:, None, :]

    def step(t, carry):
        h, y = carry
        h = a[t] * h + bu[t]                   # (bd, N)
        y = y.at[t].set(jnp.sum(h * Cm[t][None, :], axis=1))
        return h, y

    y0 = jnp.zeros((chunk, x.shape[1]), F32)
    h, y = jax.lax.fori_loop(0, chunk, step, (h_scr[...], y0))
    h_scr[...] = h
    o_ref[0] = (y + D[None, :] * x).astype(o_ref.dtype)


def ssm_scan_flat(x, dt, Bm, Cm, A_log, D, *, chunk: int = 128,
                  block_d: int = 256, interpret=None):
    """x, dt: (B, S, di); Bm, Cm: (B, S, N); A_log: (di, N); D: (di,).

    Returns y: (B, S, di).  S % chunk == 0 and di % block_d == 0 (ops.py
    pads).
    """
    B, S, di = x.shape
    N = Bm.shape[-1]
    n_d = di // block_d
    n_ck = S // chunk
    kernel = functools.partial(_ssm_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, n_d, n_ck),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, d, c: (b, c, 0)),
            pl.BlockSpec((block_d, N), lambda b, d, c: (d, 0)),
            pl.BlockSpec((block_d,), lambda b, d, c: (d,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d),
                               lambda b, d, c: (b, c, d)),
        out_shape=jax.ShapeDtypeStruct((B, S, di), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, N), F32)],
        interpret=resolve_interpret(interpret),
    )(x, dt, Bm, Cm, A_log, D)
