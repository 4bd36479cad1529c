"""Layer primitives for the FlockJAX model zoo (pure JAX reference path).

Every primitive comes as an ``init_*`` (parameter pytree) + ``*_apply`` pair
of pure functions.  Attention uses a chunked online-softmax formulation
(flash-attention structure) so peak memory is O(Sq * block_k) — this is also
the oracle the Pallas kernels are validated against.

Sharding is injected through a ``Policy`` object (see sharding.py); the
default ``NULL_POLICY`` makes every constraint a no-op so the same code runs
un-meshed in unit tests.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .config import ModelConfig

F32 = jnp.float32


# --------------------------------------------------------------------------
# sharding policy indirection
# --------------------------------------------------------------------------
class NullPolicy:
    """No-op activation-sharding policy (single-device tests)."""

    dp_size = 1     # data-parallel world size (MoE decode grouping hint)

    def __call__(self, x, name: str):
        return x


NULL_POLICY = NullPolicy()


# --------------------------------------------------------------------------
# normalisation
# --------------------------------------------------------------------------
def init_norm(cfg: ModelConfig, key, d: int):
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((d,), F32)}
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), F32), "bias": jnp.zeros((d,), F32)}
    return {}  # nonparam_ln (OLMo)


def norm_apply(cfg: ModelConfig, p, x):
    dt = x.dtype
    x = x.astype(F32)
    if cfg.norm == "rmsnorm":
        x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
        x = x * p["scale"]
    else:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        x = (x - mu) * jax.lax.rsqrt(var + 1e-5)
        if p:
            x = x * p["scale"] + p["bias"]
    return x.astype(dt)


def rms_head_norm(scale, x):
    """Per-head RMS norm (gemma3 qk-norm); x: (..., hd)."""
    dt = x.dtype
    x = x.astype(F32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6)
    return (x * scale).astype(dt)


# --------------------------------------------------------------------------
# rotary / sinusoidal positions
# --------------------------------------------------------------------------
def rope_apply(x, positions, theta: float):
    """x: (B, S, H, hd), positions: (B, S) or (S,) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(F32) * freqs          # (B,S,half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoid_pos(seq: int, d: int, offset=0, dtype=jnp.bfloat16):
    pos = jnp.arange(seq, dtype=F32) + offset
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=F32) * (math.log(10_000.0) / max(half - 1, 1)))
    ang = pos[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# --------------------------------------------------------------------------
# chunked (flash-style) attention — the jnp oracle
# --------------------------------------------------------------------------
def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      q_offset=0, kv_valid_len=None, block_k: int = 512,
                      unroll: bool = False, scale: float | None = None):
    """Online-softmax attention, scanning KV blocks.

    q: (B, Sq, H, hd);  k, v: (B, Sk, KH, hd) with H % KH == 0.
    GQA is computed with grouped einsums (q reshaped to (KH, G) heads) so
    K/V are never materialised per-q-head, and K/V stay in their storage
    dtype (f32 accumulation via preferred_element_type).
    ``q_offset``: absolute position of q[0] (prefill continuation).
    ``window`` > 0: sliding-window (local) mask  q_pos - k_pos < window.
    ``kv_valid_len``: mask out k positions >= this (padded caches).
    Returns (B, Sq, H, hd) in q.dtype.
    """
    B, Sq, H, hd = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = scale if scale is not None else hd ** -0.5
    bk = min(block_k, Sk)
    nblk = -(-Sk // bk)
    pad = nblk * bk - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kb = k.reshape(B, nblk, bk, KH, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, bk, KH, hd).transpose(1, 0, 2, 3, 4)

    # q_offset may be scalar or per-row (B,) (continuous batching slots)
    q_off = jnp.asarray(q_offset)
    if q_off.ndim == 0:
        q_off = jnp.broadcast_to(q_off, (B,))
    q_pos = q_off[:, None] + jnp.arange(Sq)[None, :]           # (B, Sq)
    valid_limit = Sk if kv_valid_len is None else kv_valid_len

    qg = (q.astype(F32) * scale).reshape(B, Sq, KH, G, hd)

    def block(carry, inp):
        m, l, acc = carry                       # (B,KH,G,Sq), ..., (..,hd)
        idx, kblk, vblk = inp                   # (B,bk,KH,hd) storage dtype
        s = jnp.einsum("bqkgh,btkh->bkgqt", qg, kblk,
                       preferred_element_type=F32)
        k_pos = idx * bk + jnp.arange(bk)
        mask = jnp.broadcast_to(k_pos[None, None, :] < valid_limit,
                                (B, Sq, bk))
        if causal:
            mask = mask & (k_pos[None, None, :] <= q_pos[:, :, None])
        if window:
            mask = mask & (q_pos[:, :, None] - k_pos[None, None, :] < window)
        neg = jnp.asarray(-1e30, F32)
        s = s + jnp.where(mask[:, None, None], 0.0, neg)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgqt,btkh->bkgqh", p, vblk,
                        preferred_element_type=F32)
        acc = acc * corr[..., None] + pv
        return (m_new, l, acc), None

    m0 = jnp.full((B, KH, G, Sq), -1e30, F32)
    l0 = jnp.zeros((B, KH, G, Sq), F32)
    a0 = jnp.zeros((B, KH, G, Sq, hd), F32)
    if unroll:
        carry = (m0, l0, a0)
        for i in range(nblk):
            carry, _ = block(carry, (jnp.int32(i), kb[i], vb[i]))
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(
            block, (m0, l0, a0), (jnp.arange(nblk), kb, vb))
    out = acc / jnp.maximum(l, 1e-37)[..., None]     # (B,KH,G,Sq,hd)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, hd).astype(q.dtype)


def blocked_attention(q, k, v, *, causal: bool, window: int = 0,
                      block_q: int = 512, block_k: int = 512,
                      scale: float | None = None, unroll: bool = False):
    """Static block-pair attention: enumerate only (q-block, kv-block)
    pairs that the causal/window mask can reach, scan over that list, and
    scatter finished q-blocks to the output.

    vs ``chunked_attention`` (which visits all Sq*Sk tiles and masks), this
    does ~2x less matmul work for causal and ~S/W less for sliding-window —
    the jnp-path analogue of the Pallas kernel's pl.when block skipping.
    Requires uniform q_offset=0 (training/prefill-from-scratch shapes).
    """
    B, Sq, H, hd = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    G = H // KH
    scale = scale if scale is not None else hd ** -0.5
    bq, bk = min(block_q, Sq), min(block_k, Sk)
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    pad_q, pad_k = nq * bq - Sq, nk * bk - Sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))

    # static pair list (row-major in qi so each q block's pairs are
    # contiguous -> single online-softmax carry, flushed on qi change)
    pairs = []
    for qi in range(nq):
        q_lo, q_hi = qi * bq, qi * bq + bq - 1
        for ki in range(nk):
            k_lo, k_hi = ki * bk, ki * bk + bk - 1
            if causal and k_lo > q_hi:
                continue
            if window and q_lo - k_hi >= window:
                continue
            pairs.append((qi, ki))
    qi_arr = jnp.asarray([p[0] for p in pairs], jnp.int32)
    ki_arr = jnp.asarray([p[1] for p in pairs], jnp.int32)
    # flag marking the last pair of each q block (flush point)
    last = jnp.asarray(
        [i + 1 == len(pairs) or pairs[i + 1][0] != pairs[i][0]
         for i in range(len(pairs))], bool)

    qb = q.reshape(B, nq, bq, KH, G, hd).astype(F32) * scale
    kb = k.reshape(B, nk, bk, KH, hd)
    vb = v.reshape(B, nk, bk, KH, hd)
    out0 = jnp.zeros((B, nq, bq, KH, G, hd), F32)

    def step(carry, inp):
        m, l, acc, out = carry
        qi, ki, flush = inp
        qt = jax.lax.dynamic_index_in_dim(qb, qi, 1, keepdims=False)
        kt = jax.lax.dynamic_index_in_dim(kb, ki, 1, keepdims=False)
        vt = jax.lax.dynamic_index_in_dim(vb, ki, 1, keepdims=False)
        s = jnp.einsum("bqkgh,btkh->bkgqt", qt, kt,
                       preferred_element_type=F32)
        q_pos = qi * bq + jnp.arange(bq)
        k_pos = ki * bk + jnp.arange(bk)
        mask = k_pos[None, :] < Sk
        if causal:
            mask = mask & (k_pos[None, :] <= q_pos[:, None])
        if window:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        s = s + jnp.where(mask[None, None, None], 0.0, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqt,btkh->bkgqh", p, vt, preferred_element_type=F32)
        o_blk = (acc / jnp.maximum(l, 1e-37)[..., None]).transpose(
            0, 3, 1, 2, 4)                                     # (B,bq,KH,G,hd)
        out = jax.lax.cond(
            flush,
            lambda o: jax.lax.dynamic_update_index_in_dim(
                o, o_blk, qi, 1),
            lambda o: o, out)
        # reset accumulators when flushing (next pair starts a new q block)
        def rst(x, fill):
            return jnp.where(flush, jnp.full_like(x, fill), x)
        return (rst(m_new, -1e30), rst(l, 0.0), rst(acc, 0.0), out), None

    m0 = jnp.full((B, KH, G, bq), -1e30, F32)
    l0 = jnp.zeros((B, KH, G, bq), F32)
    a0 = jnp.zeros((B, KH, G, bq, hd), F32)
    if unroll:     # cost-probe lowering: python loop so flops are counted
        carry = (m0, l0, a0, out0)
        for i in range(len(pairs)):
            carry, _ = step(carry, (qi_arr[i], ki_arr[i], last[i]))
        out = carry[3]
    else:
        (_, _, _, out), _ = jax.lax.scan(step, (m0, l0, a0, out0),
                                         (qi_arr, ki_arr, last))
    out = out.reshape(B, nq * bq, H, hd)[:, :Sq]
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, pos, *, window: int = 0,
                     scale: float | None = None):
    """Single-step attention over a (possibly padded) cache.

    q: (B, 1, H, hd); caches: (B, Smax, KH, hd); pos: scalar int32 = the
    current token's absolute position (its K/V already written).  Grouped
    einsums keep the cache unexpanded and in storage dtype; the softmax
    reduction over a sequence-sharded cache lowers to tiny all-reduces
    (cross-chip flash-decode).
    """
    B, _, H, hd = q.shape
    Smax, KH = k_cache.shape[1], k_cache.shape[2]
    G = H // KH
    scale = scale if scale is not None else hd ** -0.5
    qg = (q.astype(F32) * scale).reshape(B, KH, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, k_cache,
                   preferred_element_type=F32)
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))       # scalar or (B,)
    k_pos = jnp.arange(Smax)
    mask = k_pos[None, :] <= pos_b[:, None]
    if window:
        mask = mask & (pos_b[:, None] - k_pos[None, :] < window)
    s = s + jnp.where(mask[:, None, None], 0.0, jnp.asarray(-1e30, F32))
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bskh->bkgh", p / jnp.maximum(l, 1e-37), v_cache,
                     preferred_element_type=F32)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# --------------------------------------------------------------------------
# attention block (proj + rope + residual-ready output)
# --------------------------------------------------------------------------
def init_attention(cfg: ModelConfig, key, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, KH = cfg.padded_num_heads, cfg.padded_num_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = jnp.dtype(cfg.param_dtype)
    sd = d ** -0.5
    p = {
        "wq": (jax.random.normal(k1, (d, H, hd)) * sd).astype(dt),
        "wk": (jax.random.normal(k2, (d, KH, hd)) * sd).astype(dt),
        "wv": (jax.random.normal(k3, (d, KH, hd)) * sd).astype(dt),
        "wo": (jax.random.normal(k4, (H, hd, d)) * (H * hd) ** -0.5).astype(dt),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H, hd), dt)
        p["bk"] = jnp.zeros((KH, hd), dt)
        p["bv"] = jnp.zeros((KH, hd), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), F32)
        p["k_norm"] = jnp.ones((hd,), F32)
    return p


def attn_qkv(cfg: ModelConfig, p, x, positions, kind: str, policy,
             rope: bool = True):
    """Project to q, k, v (+bias, qk-norm, rope)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.qk_norm:
        q = rms_head_norm(p["q_norm"], q)
        k = rms_head_norm(p["k_norm"], k)
    if rope:
        theta = cfg.rope_theta if kind in ("attn", "global") else cfg.theta_local
        q = rope_apply(q, positions, theta)
        k = rope_apply(k, positions, theta)
    q = policy(q, "act_q")
    k = policy(k, "act_kv")
    v = policy(v, "act_kv")
    return q, k, v


def attn_out(p, o, policy):
    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return policy(y, "act")


def self_attention_train(cfg: ModelConfig, p, x, kind: str, positions,
                         policy, causal: bool = True):
    q, k, v = attn_qkv(cfg, p, x, positions, kind, policy)
    window = cfg.window_size if kind in ("local", "swa") else 0
    if cfg.use_pallas:
        from repro.kernels.flash_attention.ops import flash_attention
        o = flash_attention(q, k, v, causal=causal, window=window)
    elif cfg.attn_impl == "blocked":
        o = blocked_attention(q, k, v, causal=causal, window=window,
                              block_q=cfg.attn_block_k,
                              block_k=cfg.attn_block_k,
                              unroll=cfg.unroll_inner)
    else:
        o = chunked_attention(q, k, v, causal=causal, window=window,
                              block_k=cfg.attn_block_k,
                              unroll=cfg.unroll_inner)
    o = policy(o, "act_q")
    return attn_out(p, o, policy), (k, v)


def quantize_kv(x):
    """Symmetric int8 per-(token, head) quantization:
    x (B, S, KH, hd) -> (int8 values, f32 scales (B, S, KH, 1))."""
    scale = jnp.max(jnp.abs(x.astype(F32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(F32) / scale), -127, 127).astype(
        jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype):
    return (q.astype(F32) * scale).astype(dtype)


def self_attention_decode(cfg: ModelConfig, p, x, kind: str, cache, pos,
                          policy):
    """x: (B, 1, d). cache: {"k","v"}: (B, Smax, KH, hd). Returns (y, cache).

    The cache write uses a masked ``where`` along the (sharded) sequence dim
    instead of dynamic_update_slice: a runtime-dynamic DUS on a sharded axis
    makes GSPMD all-gather the whole cache (verified on the 16x16 mesh),
    while the masked write stays shard-local.

    kv_quant="int8" stores the cache as int8 with per-(token, head) scales:
    ~2x less decode HBM traffic and cache footprint — what lets
    qwen1.5-32b's 5.5TB bf16 decode_32k cache fit one pod (§Perf).
    """
    B = x.shape[0]
    pos_b = jnp.broadcast_to(jnp.asarray(pos), (B,))       # scalar or (B,)
    positions = pos_b[:, None].astype(jnp.int32)
    q, k, v = attn_qkv(cfg, p, x, positions, kind, policy)
    sel = (jnp.arange(cache["k"].shape[1])[None, :]
           == pos_b[:, None])[:, :, None, None]
    if cfg.kv_quant == "int8":
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        ck = policy(jnp.where(sel, kq, cache["k"]), "kv_cache")
        cv = policy(jnp.where(sel, vq, cache["v"]), "kv_cache")
        cks = policy(jnp.where(sel, ks, cache["k_scale"]), "kv_cache")
        cvs = policy(jnp.where(sel, vs, cache["v_scale"]), "kv_cache")
        k_use = dequantize_kv(ck, cks, cfg.compute_dtype)
        v_use = dequantize_kv(cv, cvs, cfg.compute_dtype)
        new_cache = {"k": ck, "v": cv, "k_scale": cks, "v_scale": cvs}
    else:
        ck = policy(jnp.where(sel, k.astype(cache["k"].dtype), cache["k"]),
                    "kv_cache")
        cv = policy(jnp.where(sel, v.astype(cache["v"].dtype), cache["v"]),
                    "kv_cache")
        k_use, v_use = ck, cv
        new_cache = {"k": ck, "v": cv}
    window = cfg.window_size if kind in ("local", "swa") else 0
    q = policy(q, "act_q_decode")
    if cfg.use_pallas:
        from repro.kernels.decode_attention.ops import \
            decode_attention as decode_attention_pallas
        o = decode_attention_pallas(q, k_use, v_use, pos_b, window=window)
    else:
        o = decode_attention(q, k_use, v_use, pos, window=window)
    return attn_out(p, o, policy), new_cache


def self_attention_extend(cfg: ModelConfig, p, x, kind: str, cache, off,
                          policy):
    """Chunked-prefill (Sarathi-style): process a chunk of C prompt tokens
    against an existing cache.  x: (B, C, d); off: scalar or (B,) — number
    of tokens already cached per row.  Exact for every arch (no padding).
    """
    B, C, _ = x.shape
    off_b = jnp.broadcast_to(jnp.asarray(off), (B,))
    positions = off_b[:, None] + jnp.arange(C)[None, :]
    q, k, v = attn_qkv(cfg, p, x, positions, kind, policy)
    # write the chunk into the cache at [off, off+C) (gather-style select,
    # shard-local on a sequence-sharded cache)
    Smax = cache["k"].shape[1]
    idx = jnp.arange(Smax)[None, :] - off_b[:, None]           # (B, Smax)
    sel = (idx >= 0) & (idx < C)
    safe = jnp.clip(idx, 0, C - 1)
    def put(cache_arr, chunk):
        gathered = jnp.take_along_axis(
            chunk.astype(cache_arr.dtype), safe[:, :, None, None], axis=1)
        return jnp.where(sel[:, :, None, None], gathered, cache_arr)
    ck = policy(put(cache["k"], k), "kv_cache")
    cv = policy(put(cache["v"], v), "kv_cache")
    window = cfg.window_size if kind in ("local", "swa") else 0
    q = policy(q, "act_q")
    o = chunked_attention(q, ck, cv, causal=True, window=window,
                          q_offset=off_b, block_k=cfg.attn_block_k,
                          unroll=cfg.unroll_inner)
    o = policy(o, "act_q")
    return attn_out(p, o, policy), {"k": ck, "v": cv}


def cross_attention(cfg: ModelConfig, p, x, enc_k, enc_v, policy):
    """Decoder cross-attention over precomputed encoder K/V (no rope)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    q = policy(q, "act_q")
    o = chunked_attention(q, enc_k, enc_v, causal=False,
                          block_k=cfg.attn_block_k, unroll=cfg.unroll_inner)
    o = policy(o, "act_q")
    return attn_out(p, o, policy)


def encode_cross_kv(cfg: ModelConfig, p, enc_out, policy):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"])
    return policy(k, "act_kv"), policy(v, "act_kv")


# --------------------------------------------------------------------------
# dense FFN
# --------------------------------------------------------------------------
def init_ffn(cfg: ModelConfig, key, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w1": (jax.random.normal(k1, (d, f)) * d ** -0.5).astype(dt),
         "w2": (jax.random.normal(k2, (f, d)) * f ** -0.5).astype(dt)}
    if cfg.glu:
        p["w3"] = (jax.random.normal(k3, (d, f)) * d ** -0.5).astype(dt)
    return p


def _act(cfg: ModelConfig, x):
    return jax.nn.silu(x) if cfg.act == "silu" else jax.nn.gelu(x)


def ffn_apply(cfg: ModelConfig, p, x, policy):
    h = _act(cfg, jnp.einsum("bsd,df->bsf", x, p["w1"]))
    if cfg.glu:
        h = h * jnp.einsum("bsd,df->bsf", x, p["w3"])
    h = policy(h, "act_ff")
    y = jnp.einsum("bsf,fd->bsd", h, p["w2"])
    return policy(y, "act")


# --------------------------------------------------------------------------
# Mixture-of-Experts FFN (top-k, shared experts, capacity-dropped dispatch)
# --------------------------------------------------------------------------
def init_moe(cfg: ModelConfig, key):
    d, E, fe = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p = {
        "router": (jax.random.normal(ks[0], (d, E)) * d ** -0.5).astype(F32),
        "w1": (jax.random.normal(ks[1], (E, d, fe)) * d ** -0.5).astype(dt),
        "w2": (jax.random.normal(ks[2], (E, fe, d)) * fe ** -0.5).astype(dt),
    }
    if cfg.glu:
        p["w3"] = (jax.random.normal(ks[3], (E, d, fe)) * d ** -0.5).astype(dt)
    if cfg.num_shared_experts:
        shared_cfg = cfg.replace(d_ff=cfg.moe_d_ff * cfg.num_shared_experts)
        p["shared"] = init_ffn(shared_cfg, ks[4], shared_cfg.d_ff)
    return p


def moe_apply(cfg: ModelConfig, p, x, policy):
    """Group-local capacity dispatch — see DESIGN.md §6.

    x: (B, S, d).  Dispatch groups are batch rows for full sequences, so
    every gather/scatter stays local to the data shard; for decode (S == 1)
    batch rows are regrouped into ``policy.dp_size`` groups so the capacity
    padding is amortised across the per-shard batch instead of paying
    E*C slots per single token.  Expert FFNs are tensor-parallel over
    ``model`` (experts replicated in count, sharded in d_ff).
    """
    B0, S0, d = x.shape
    orig_shape = x.shape
    if S0 == 1 and B0 > 1:
        G = min(B0, max(policy.dp_size, 1))
        x = x.reshape(G, B0 // G, d)
    B, S, _ = x.shape
    E, K = cfg.num_experts, cfg.top_k
    C = cfg.moe_capacity(S)

    logits = jnp.einsum("bsd,de->bse", x.astype(F32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = jax.lax.top_k(probs, K)                 # (B,S,K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # ---- slotting: rank of each (token, choice) within its expert ----
    ef = eidx.reshape(B, S * K)                          # (B, T)
    order = jnp.argsort(ef, axis=-1, stable=True)        # (B, T)
    sorted_e = jnp.take_along_axis(ef, order, axis=-1)
    counts = jax.nn.one_hot(ef, E, dtype=jnp.int32).sum(axis=1)     # (B, E)
    starts = jnp.cumsum(counts, axis=-1) - counts                   # (B, E)
    ranks = jnp.arange(S * K)[None, :] - jnp.take_along_axis(
        starts, sorted_e, axis=-1)                                  # (B, T)
    keep = ranks < C
    dest = jnp.where(keep, sorted_e * C + ranks, E * C)  # OOB sentinel slot
    src_tok = order // K                                 # token of assignment
    wts = jnp.take_along_axis(gate.reshape(B, S * K), order, axis=-1)

    bidx = jnp.arange(B)[:, None]
    # token-index table (B, E*C+1): which token fills each expert slot
    table = jnp.full((B, E * C + 1), S, jnp.int32).at[bidx, dest].set(
        src_tok, mode="drop")[:, :E * C]
    wtab = jnp.zeros((B, E * C + 1), F32).at[bidx, dest].set(
        wts, mode="drop")[:, :E * C]

    x_pad = jnp.concatenate([x, jnp.zeros((B, 1, d), x.dtype)], axis=1)
    gathered = jnp.take_along_axis(x_pad, table[..., None], axis=1)
    gathered = gathered.reshape(B, E, C, d)
    gathered = policy(gathered, "moe_gathered")

    h = _act(cfg, jnp.einsum("becd,edf->becf", gathered, p["w1"]))
    if cfg.glu:
        h = h * jnp.einsum("becd,edf->becf", gathered, p["w3"])
    h = policy(h, "moe_hidden")
    out_e = jnp.einsum("becf,efd->becd", h, p["w2"])
    out_e = out_e.reshape(B, E * C, d) * wtab[..., None].astype(out_e.dtype)

    y = jnp.zeros((B, S + 1, d), out_e.dtype).at[bidx, table].add(out_e)[:, :S]
    y = y.reshape(orig_shape)
    x = x.reshape(orig_shape)
    y = policy(y, "act")

    if cfg.num_shared_experts:
        y = y + ffn_apply(cfg.replace(d_ff=cfg.moe_d_ff * cfg.num_shared_experts),
                          p["shared"], x, policy)

    # Switch-style load-balance aux loss (returned for train metrics)
    frac = counts.astype(F32).sum(0) / (B * S * K)           # (E,)
    imp = probs.mean(axis=(0, 1))                            # (E,)
    aux = E * jnp.sum(frac * imp)
    return y, aux


# --------------------------------------------------------------------------
# linear recurrence scan  h_t = a_t * h_{t-1} + b_t   (chunked, assoc within)
# --------------------------------------------------------------------------
def _assoc_combine(left, right):
    al, bl = left
    ar, br = right
    return ar * al, ar * bl + br


def linear_scan(a, b, h0=None, *, chunk: int = 256, unroll: bool = False):
    """Scan along axis 1.  a, b: (B, S, ...). Returns (h_all, h_last)."""
    B, S = a.shape[:2]
    ck = min(chunk, S)
    nchunk = -(-S // ck)
    pad = nchunk * ck - S
    if pad:
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2),
                    constant_values=1.0)
        b = jnp.pad(b, [(0, 0), (0, pad)] + [(0, 0)] * (b.ndim - 2))
    tail = a.shape[2:]
    ac = a.reshape(B, nchunk, ck, *tail).transpose(1, 0, 2, *range(3, a.ndim + 1))
    bc = b.reshape(B, nchunk, ck, *tail).transpose(1, 0, 2, *range(3, b.ndim + 1))

    if h0 is None:
        h0 = jnp.zeros((B, *tail), a.dtype)

    def chunk_step(h_in, inp):
        a_i, b_i = inp                                   # (B, ck, ...)
        A, Bv = jax.lax.associative_scan(_assoc_combine, (a_i, b_i), axis=1)
        h_chunk = Bv + A * h_in[:, None]
        return h_chunk[:, -1], h_chunk

    if unroll:
        outs, h = [], h0
        for i in range(nchunk):
            h, hc = chunk_step(h, (ac[i], bc[i]))
            outs.append(hc)
        h_all = jnp.stack(outs, 0)
    else:
        h, h_all = jax.lax.scan(chunk_step, h0, (ac, bc))
    h_all = h_all.transpose(1, 0, 2, *range(3, a.ndim + 1)).reshape(
        B, nchunk * ck, *tail)[:, :S]
    return h_all, h


# --------------------------------------------------------------------------
# causal depthwise conv (width 4) — shared by Mamba and RG-LRU blocks
# --------------------------------------------------------------------------
def causal_conv(x, w, b, state=None):
    """x: (B, S, C); w: (cw, C); state: (B, cw-1, C) prior context or None.

    Returns (y, new_state) where new_state is the trailing cw-1 inputs.
    """
    cw = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], cw - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(cw)) + b
    new_state = xp[:, -(cw - 1):] if cw > 1 else state
    return y, new_state


# --------------------------------------------------------------------------
# Mamba-1 selective SSM block
# --------------------------------------------------------------------------
def init_mamba(cfg: ModelConfig, key):
    d, di, s, r, cw = (cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank,
                       cfg.conv_width)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    return {
        "in_proj": (jax.random.normal(ks[0], (d, 2 * di)) * d ** -0.5).astype(dt),
        "conv_w": (jax.random.normal(ks[1], (cw, di)) * cw ** -0.5).astype(dt),
        "conv_b": jnp.zeros((di,), dt),
        "x_proj": (jax.random.normal(ks[2], (di, r + 2 * s)) * di ** -0.5).astype(dt),
        "dt_proj": (jax.random.normal(ks[3], (r, di)) * r ** -0.5).astype(dt),
        "dt_bias": jnp.full((di,), -2.0, F32),
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, s + 1, dtype=F32), (di, s)) + 0.0),
        "D": jnp.ones((di,), F32),
        "out_proj": (jax.random.normal(ks[4], (di, d)) * di ** -0.5).astype(dt),
    }


def fused_selective_scan(cfg, x_c, dt, Bm, Cm, A_log, D, h0=None,
                         unroll=False):
    """Chunked selective scan with discretisation + C-projection fused into
    the chunk body (jax.checkpoint'ed): the (B, chunk, di, state) tensors
    are transients of one chunk, never a full-sequence residual — the jnp
    mirror of the ssm_scan Pallas kernel's VMEM-only Ā/B̄u.
    Returns (y (B,S,di) f32, h_last (B,di,state) f32)."""
    B, S, di = x_c.shape
    s = Bm.shape[-1]
    ck = min(cfg.scan_chunk, S)
    nck = -(-S // ck)
    pad = nck * ck - S
    if pad:
        x_c = jnp.pad(x_c, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    A = -jnp.exp(A_log.astype(F32))

    def to_chunks(t):
        return t.reshape(B, nck, ck, *t.shape[2:]).transpose(
            1, 0, 2, *range(3, t.ndim + 1))

    xs = (to_chunks(x_c), to_chunks(dt), to_chunks(Bm), to_chunks(Cm))

    @jax.checkpoint
    def chunk_body(h_in, inp):
        xq, dtq, Bq, Cq = inp
        dtf = dtq.astype(F32)
        a = jnp.exp(dtf[..., None] * A)                  # (B,ck,di,s)
        bu = (dtf * xq.astype(F32))[..., None] * Bq.astype(
            F32)[:, :, None, :]
        Ac, Buc = jax.lax.associative_scan(_assoc_combine, (a, bu), axis=1)
        hc = Buc + Ac * h_in[:, None]
        y = (hc * Cq.astype(F32)[:, :, None, :]).sum(-1)
        return hc[:, -1], y

    if h0 is None:
        h0 = jnp.zeros((B, di, s), F32)
    if unroll:
        ys, h = [], h0
        for i in range(nck):
            h, yc = chunk_body(h, tuple(t[i] for t in xs))
            ys.append(yc)
        y = jnp.stack(ys, 0)
    else:
        h, y = jax.lax.scan(chunk_body, h0, xs)
    y = y.transpose(1, 0, 2, 3).reshape(B, nck * ck, di)[:, :S]
    return y + D.astype(F32) * x_c.astype(F32)[:, :S], h


def _mamba_core(cfg, p, x_c, policy, h0=None, return_state=False):
    """x_c: (B, S, di) post-conv activations -> (y, h_last)."""
    r, s = cfg.dt_rank, cfg.ssm_state
    proj = jnp.einsum("bsi,ij->bsj", x_c, p["x_proj"])
    dt_raw, Bm, Cm = jnp.split(proj, [r, r + s], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,ri->bsi", dt_raw, p["dt_proj"]).astype(F32)
        + p["dt_bias"])                                          # (B,S,di)
    if cfg.use_pallas and h0 is None and not return_state:
        from repro.kernels.ssm_scan.ops import ssm_scan
        y = ssm_scan(x_c, dt.astype(x_c.dtype), Bm, Cm, p["A_log"], p["D"])
        return y, None
    if cfg.ssm_fuse == "chunk":
        y, h_last = fused_selective_scan(cfg, x_c, dt, Bm, Cm, p["A_log"],
                                         p["D"], h0=h0,
                                         unroll=cfg.unroll_inner)
        return y.astype(x_c.dtype), (h_last if return_state else None)
    A = -jnp.exp(p["A_log"])                                     # (di, s)
    a = jnp.exp(dt[..., None] * A)                               # (B,S,di,s)
    bu = (dt * x_c.astype(F32))[..., None] * Bm.astype(F32)[:, :, None, :]
    h_all, h_last = linear_scan(a, bu, h0, chunk=cfg.scan_chunk,
                                unroll=cfg.unroll_inner)
    y = (h_all * Cm.astype(F32)[:, :, None, :]).sum(-1)          # (B,S,di)
    y = y + p["D"] * x_c.astype(F32)
    return y.astype(x_c.dtype), (h_last if return_state else None)


def mamba_apply_train(cfg: ModelConfig, p, x, policy):
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xz = policy(xz, "act_inner2")
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_c, _ = causal_conv(x_in, p["conv_w"], p["conv_b"])
    x_c = jax.nn.silu(x_c)
    y, _ = _mamba_core(cfg, p, x_c, policy)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bsi,id->bsd", y, p["out_proj"])
    return policy(out, "act")


def mamba_apply_decode(cfg: ModelConfig, p, x, cache, policy):
    """x: (B, 1, d); cache: {"conv": (B, cw-1, di), "ssm": (B, di, s)}."""
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xz = policy(xz, "act_inner2")
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_c, conv_state = causal_conv(x_in, p["conv_w"], p["conv_b"],
                                  state=cache["conv"])
    x_c = jax.nn.silu(x_c)
    y, h_last = _mamba_core(cfg, p, x_c, policy, h0=cache["ssm"],
                            return_state=True)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bsi,id->bsd", y, p["out_proj"])
    new_cache = {"conv": policy(conv_state, "ssm_conv"),
                 "ssm": policy(h_last, "ssm_state")}
    return policy(out, "act"), new_cache


def init_mamba_cache(cfg: ModelConfig, B: int, dtype):
    return {"conv": jnp.zeros((B, cfg.conv_width - 1, cfg.d_inner), dtype),
            "ssm": jnp.zeros((B, cfg.d_inner, cfg.ssm_state), F32)}


# --------------------------------------------------------------------------
# RG-LRU block (Griffin / RecurrentGemma recurrent block)
# --------------------------------------------------------------------------
def init_rglru(cfg: ModelConfig, key):
    d, di, cw, nb = cfg.d_model, cfg.d_inner, cfg.conv_width, cfg.rglru_blocks
    bs = di // nb
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    return {
        "w_x": (jax.random.normal(ks[0], (d, di)) * d ** -0.5).astype(dt),
        "w_gate": (jax.random.normal(ks[1], (d, di)) * d ** -0.5).astype(dt),
        "conv_w": (jax.random.normal(ks[2], (cw, di)) * cw ** -0.5).astype(dt),
        "conv_b": jnp.zeros((di,), dt),
        "rg_a": (jax.random.normal(ks[3], (nb, bs, bs)) * bs ** -0.5).astype(dt),
        "rg_a_b": jnp.zeros((di,), F32),
        "rg_x": (jax.random.normal(ks[4], (nb, bs, bs)) * bs ** -0.5).astype(dt),
        "rg_x_b": jnp.zeros((di,), F32),
        "lam": jnp.full((di,), 2.0, F32),   # sigmoid(lam)≈0.88 base decay
        "out_proj": (jax.random.normal(ks[5], (di, d)) * di ** -0.5).astype(dt),
    }


def _blockdiag(x, w, nb):
    B, S, di = x.shape
    xb = x.reshape(B, S, nb, di // nb)
    return jnp.einsum("bsnq,nqp->bsnp", xb, w).reshape(B, S, di)


_RG_C = 8.0


def _rglru_core(cfg, p, x_c, h0=None, return_state=False):
    nb = cfg.rglru_blocks
    r = jax.nn.sigmoid(_blockdiag(x_c, p["rg_a"], nb).astype(F32) + p["rg_a_b"])
    i = jax.nn.sigmoid(_blockdiag(x_c, p["rg_x"], nb).astype(F32) + p["rg_x_b"])
    log_a = -_RG_C * r * jax.nn.softplus(p["lam"])      # (B,S,di) <= 0
    a = jnp.exp(log_a)
    gated = i * x_c.astype(F32)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6)) * gated
    if cfg.use_pallas and h0 is None and not return_state:
        from repro.kernels.rg_lru.ops import rg_lru
        h_all = rg_lru(a, b)
        return h_all, None
    h_all, h_last = linear_scan(a, b, h0, chunk=cfg.scan_chunk,
                                unroll=cfg.unroll_inner)
    return h_all, (h_last if return_state else None)


def rglru_apply_train(cfg: ModelConfig, p, x, policy):
    xb = jnp.einsum("bsd,di->bsi", x, p["w_x"])
    g = jax.nn.gelu(jnp.einsum("bsd,di->bsi", x, p["w_gate"]))
    xb = policy(xb, "act_inner")
    g = policy(g, "act_inner")
    x_c, _ = causal_conv(xb, p["conv_w"], p["conv_b"])
    h, _ = _rglru_core(cfg, p, x_c)
    y = (h * g.astype(F32)).astype(x.dtype)
    out = jnp.einsum("bsi,id->bsd", y, p["out_proj"])
    return policy(out, "act")


def rglru_apply_decode(cfg: ModelConfig, p, x, cache, policy):
    xb = jnp.einsum("bsd,di->bsi", x, p["w_x"])
    g = jax.nn.gelu(jnp.einsum("bsd,di->bsi", x, p["w_gate"]))
    xb = policy(xb, "act_inner")
    x_c, conv_state = causal_conv(xb, p["conv_w"], p["conv_b"],
                                  state=cache["conv"])
    h, h_last = _rglru_core(cfg, p, x_c, h0=cache["h"], return_state=True)
    y = (h * g.astype(F32)).astype(x.dtype)
    out = jnp.einsum("bsi,id->bsd", y, p["out_proj"])
    new_cache = {"conv": policy(conv_state, "ssm_conv"),
                 "h": policy(h_last, "ssm_state")}
    return policy(out, "act"), new_cache


def init_rglru_cache(cfg: ModelConfig, B: int, dtype):
    return {"conv": jnp.zeros((B, cfg.conv_width - 1, cfg.d_inner), dtype),
            "h": jnp.zeros((B, cfg.d_inner), F32)}
