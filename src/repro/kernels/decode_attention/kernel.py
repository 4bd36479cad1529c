"""Flash-decode Pallas TPU kernel: one query token vs a long KV cache.

GPU flash-decoding splits the KV sequence across SMs and combines partial
(m, l, acc) triples with a second reduction kernel.  On TPU the grid's
last dimension already iterates sequentially with VMEM-resident state, so
the same split-K idea becomes: stream S-blocks of the cache HBM->VMEM,
keep the running softmax state for all G grouped q-heads in VMEM scratch,
flush once.  HBM traffic = exactly one pass over the cache (the roofline
floor for decode), with no (B, H, S) score materialisation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

F32 = jnp.float32
NEG = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, n_s: int, block_s: int,
                   kv_len: int, window: int):
    b = pl.program_id(0)
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG, F32)
        l_scr[...] = jnp.zeros(l_scr.shape, F32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, F32)

    q = q_ref[0].astype(F32) * scale                    # (G, hd)
    k = k_ref[0]                                        # (bs, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32)  # (G, bs)
    pos = pos_ref[b]
    k_pos = si * block_s + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (k_pos <= pos) & (k_pos < kv_len)
    if window:
        mask &= pos - k_pos < window
    s = jnp.where(mask, s, NEG)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    pv = jax.lax.dot_general(p, v_ref[0], (((1,), (0,)), ((), ())),
                             preferred_element_type=F32)
    acc_scr[...] = acc_scr[...] * corr[:, None] + pv
    m_scr[...] = m_new

    @pl.when(si == n_s - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_flat(q, k_cache, v_cache, pos, *,
                          scale: float | None = None, block_s: int = 512,
                          kv_len: int | None = None, window: int = 0,
                          interpret=None):
    """q: (BKH, G, hd); caches: (BKH, S, hd); pos: (BKH,) int32 position
    of each row's query token.  Key ``t`` of a row is attended iff
    ``t <= pos``, ``t < kv_len`` and, with a ``window``,
    ``pos - t < window``.  The mask is built in the kernel from the
    scalar-prefetched ``pos``, so no mask array is read from HBM.

    Returns (BKH, G, hd).  S must be a multiple of block_s (ops.py pads
    and passes the true length as ``kv_len``).
    """
    BKH, G, hd = q.shape
    S = k_cache.shape[1]
    n_s = S // block_s
    scale = scale if scale is not None else hd ** -0.5
    kv_len = S if kv_len is None else kv_len
    kernel = functools.partial(_decode_kernel, scale=scale, n_s=n_s,
                               block_s=block_s, kv_len=kv_len,
                               window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(BKH, n_s),
            in_specs=[
                pl.BlockSpec((1, G, hd), lambda b, si, pos: (b, 0, 0)),
                pl.BlockSpec((1, block_s, hd),
                             lambda b, si, pos: (b, si, 0)),
                pl.BlockSpec((1, block_s, hd),
                             lambda b, si, pos: (b, si, 0)),
            ],
            out_specs=pl.BlockSpec((1, G, hd), lambda b, si, pos: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G,), F32),
                pltpu.VMEM((G,), F32),
                pltpu.VMEM((G, hd), F32),
            ]),
        out_shape=jax.ShapeDtypeStruct((BKH, G, hd), q.dtype),
        interpret=resolve_interpret(interpret),
    )(pos, q, k_cache, v_cache)
