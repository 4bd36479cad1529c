"""Flash-attention Pallas TPU kernel (prefill path).

TPU adaptation of the FlashAttention-2 schedule:
  * grid (B*H, n_q_blocks, n_kv_blocks); the last grid dim is sequential on
    a TensorCore, so the online-softmax running state (m, l, acc) lives in
    VMEM scratch and carries across KV blocks for free — no atomics, no
    inter-block synchronisation (the CUDA pain point simply disappears);
  * (block_q x block_k) tiles sized for the MXU (multiples of 128) and a
    VMEM working set of ~(bq*hd + bk*hd + bq*bk) * 4B;
  * causal / sliding-window masks are evaluated per *block* first —
    fully-masked KV blocks are skipped with pl.when, so SWA prefill does
    O(S*W) work, not O(S^2);
  * GQA: the KV block index map divides the flattened (B*H) row down to its
    (B*KH) source row, so KV tiles are fetched once per group.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int, block_q: int,
                  block_k: int, n_k: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG, F32)
        l_scr[...] = jnp.zeros(l_scr.shape, F32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, F32)

    q_start = qi * block_q
    k_start = ki * block_k
    relevant = k_start < kv_len
    if causal:
        relevant &= k_start <= q_start + block_q - 1
    if window:
        relevant &= q_start - (k_start + block_k - 1) < window

    @pl.when(relevant)
    def _body():
        q = q_ref[0].astype(F32) * scale                       # (bq, hd)
        k = k_ref[0]                                           # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)    # (bq, bk)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < kv_len
        if causal:
            mask &= k_pos <= q_pos
        if window:
            mask &= q_pos - k_pos < window
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

    @pl.when(ki == n_k - 1)
    def _flush():
        l = jnp.maximum(l_scr[...], 1e-37)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q, k, v, *, causal: bool = True, window: int = 0,
                         scale: float | None = None, block_q: int = 128,
                         block_k: int = 128, kv_len: int | None = None,
                         interpret=None):
    """q: (BH, Sq, hd); k, v: (BKH, Sk, hd); BH % BKH == 0.

    Sq/Sk must be padded to block multiples by the caller (ops.py does it);
    ``kv_len`` masks the KV padding.
    """
    BH, Sq, hd = q.shape
    BKH, Sk, _ = k.shape
    G = BH // BKH
    scale = scale if scale is not None else hd ** -0.5
    n_q = Sq // block_q
    n_k = Sk // block_k
    kv_len = Sk if kv_len is None else kv_len

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k, kv_len=kv_len)

    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
            pl.BlockSpec((1, block_k, hd),
                         lambda bh, qi, ki, G=G: (bh // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), F32),
            pltpu.VMEM((block_q,), F32),
            pltpu.VMEM((block_q, hd), F32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
