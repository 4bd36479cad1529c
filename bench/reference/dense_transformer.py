"""Plain float32 reference of a dense decoder-only transformer.

It serves every configuration whose file names ``"reference":
"dense_transformer"``.  It imports nothing of the system under test: the
layer equations, and the recipe that turns a seed into weights, are
written out here from the configuration's ``model`` block.

Weights.  The served model draws its random weights from
``PRNGKey(seed)`` with this key tree, and so does the reference:

    ks = split(key, 8)
    embed     normal(ks[0], (V, d)) * d**-0.5
    layer r   key = fold_in(ks[2], 0); r+1 times: key, sub = split(key);
              then sub, kr = split(sub); k = split(kr, 6)
              attention from k[1] (wq, wk, wv, wo from split(k[1], 4)),
              feed-forward from k[3] (w1, w2, w3 from split(k[3], 3))
    lm_head   normal(ks[3], (d, V)) * d**-0.5   (untied models only)

Each weight is rounded to the stated ``torch_dtype`` (bfloat16) and then
held in float32, so the reference sees the values that are served.
Norms have no learnt parameters at initialisation (unit scale).

``served_weights()`` gives every one of them under the path the program
stores it at, the ``L`` layers stacked on a leading axis (one stage of
one block):

    embed                      (V, d)
    stages/0/b0/attn/wq        (L, d, H, hd)    wk, wv (L, d, KH, hd)
    stages/0/b0/attn/wo        (L, H, hd, d)
    stages/0/b0/ffn/w1, w3     (L, d, f)        w2 (L, f, d)
    stages/0/b0/ln1/scale      (L, d)           ln2 alike; RMSNorm only,
    final_norm/scale           (d,)             ones; LayerNorm has none
    lm_head                    (d, V)           untied models only

Layer, per the model's published description:

    h  = norm(x);  q, k, v = h Wq, h Wk, h Wv;  rotary positions (the
         half-split form) on q and k;  causal softmax(q k^T / sqrt(hd)) v
    x += attn Wo;  h = norm(x);  x += (silu(h W1) * (h W3)) W2
    logits = norm(x) E^T (tied) or norm(x) W_head

``norm`` is LayerNorm without parameters (OLMo, eps 1e-5) or RMSNorm
(eps as the file states).  Every matrix product runs at float32 under
``Precision.HIGHEST``.  ``mode="int8"`` is the lower-precision control:
each weight is rounded to int8 per output channel and each activation
entering a weight product to int8 per token, then multiplied as above.

Work is done layer by layer: one jitted layer function draws that
layer's weights from its key and applies them, so a model larger than
the chip's free memory in float32 still fits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _q8(a, axes):
    """Symmetric int8 rounding with one scale per slice over ``axes``."""
    s = jnp.maximum(jnp.max(jnp.abs(a), axis=axes, keepdims=True) / 127.0,
                    1e-30)
    return jnp.clip(jnp.round(a / s), -127, 127) * s


class DenseTransformer:
    def __init__(self, model: dict, seed: int):
        m = model
        self.d = int(m["hidden_size"])
        self.L = int(m["num_hidden_layers"])
        self.H = int(m["num_attention_heads"])
        self.KH = int(m["num_key_value_heads"])
        self.hd = int(m["head_dim"])
        self.f = int(m["intermediate_size"])
        self.V = int(m["vocab_size"])
        self.tied = bool(m["tie_word_embeddings"])
        self.theta = float(m["rope_theta"])
        self.norm = m["norm"]
        self.eps = float(m["norm_eps"])
        self.dtype = jnp.dtype(m["torch_dtype"])
        if m.get("hidden_act", "silu") != "silu":
            raise ValueError("dense_transformer implements SwiGLU only")
        if self.norm not in ("layernorm_nonparametric", "rmsnorm"):
            raise ValueError(f"unknown norm {self.norm!r}")
        self.seed = int(seed) % 2**32
        ks = jax.random.split(jax.random.PRNGKey(self.seed), 8)
        self._top = ks
        key = jax.random.fold_in(ks[2], 0)
        self._layer_keys = []
        for _ in range(self.L):
            key, sub = jax.random.split(key)
            sub, kr = jax.random.split(sub)
            self._layer_keys.append(kr)
        self._layer = jax.jit(self._layer_fn, static_argnames=("mode",))
        self._head = jax.jit(self._head_fn, static_argnames=("mode",))
        self._pool = jax.jit(self._pool_fn)

    # ---------------------------------------------------------- weights
    def _w(self, key, shape, scale):
        w = jax.random.normal(key, shape) * scale
        return w.astype(self.dtype).astype(F32)

    def _layer_weights(self, kr):
        """One layer's weights, keyed by their path in the layer."""
        d, H, KH, hd, f = self.d, self.H, self.KH, self.hd, self.f
        k = jax.random.split(kr, 6)
        a1, a2, a3, a4 = jax.random.split(k[1], 4)
        f1, f2, f3 = jax.random.split(k[3], 3)
        return {"attn/wq": self._w(a1, (d, H, hd), d ** -0.5),
                "attn/wk": self._w(a2, (d, KH, hd), d ** -0.5),
                "attn/wv": self._w(a3, (d, KH, hd), d ** -0.5),
                "attn/wo": self._w(a4, (H, hd, d), (H * hd) ** -0.5),
                "ffn/w1": self._w(f1, (d, f), d ** -0.5),
                "ffn/w2": self._w(f2, (f, d), f ** -0.5),
                "ffn/w3": self._w(f3, (d, f), d ** -0.5)}

    def embed_table(self):
        return jax.jit(lambda k: self._w(k, (self.V, self.d),
                                         self.d ** -0.5))(self._top[0])

    def _head_weight(self, table):
        if self.tied:
            return table.T
        return self._w(self._top[3], (self.d, self.V), self.d ** -0.5)

    def served_weights(self) -> dict:
        """Every served weight by its path (module docstring)."""
        layers = [self._layer_weights(kr) for kr in self._layer_keys]
        out = {"embed": self.embed_table()}
        out.update({f"stages/0/b0/{n}": jnp.stack([w[n] for w in layers])
                    for n in layers[0]})
        if self.norm == "rmsnorm":
            for n in ("ln1", "ln2"):
                out[f"stages/0/b0/{n}/scale"] = jnp.ones((self.L, self.d),
                                                         F32)
            out["final_norm/scale"] = jnp.ones((self.d,), F32)
        if not self.tied:
            out["lm_head"] = self._head_weight(None)
        return out

    # ---------------------------------------------------------- layers
    def _norm(self, x):
        if self.norm == "rmsnorm":
            return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + self.eps)
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + self.eps)

    @staticmethod
    def _mm(spec, x, w, mode, w_axes):
        if mode == "int8":
            x = _q8(x, (-1,))
            w = _q8(w, w_axes)
        return jnp.einsum(spec, x, w, precision=HIGHEST)

    def _rope(self, x):
        S, half = x.shape[1], self.hd // 2
        freqs = self.theta ** (-jnp.arange(half, dtype=F32) / half)
        ang = jnp.arange(S, dtype=F32)[:, None] * freqs
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _layer_fn(self, x, kr, mode="f32"):
        """x: (B, S, d) float32, every row from position 0, causal."""
        w = self._layer_weights(kr)
        B, S, _ = x.shape
        h = self._norm(x)
        q = self._rope(self._mm("bsd,dhk->bshk", h, w["attn/wq"], mode,
                                (0,)))
        k = self._rope(self._mm("bsd,dhk->bshk", h, w["attn/wk"], mode,
                                (0,)))
        v = self._mm("bsd,dhk->bshk", h, w["attn/wv"], mode, (0,))
        g = self.H // self.KH
        k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=HIGHEST)
        s = s * self.hd ** -0.5
        causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqs,bshk->bqhk", p, v, precision=HIGHEST)
        x = x + self._mm("bshk,hkd->bsd", o, w["attn/wo"], mode, (0, 1))
        h = self._norm(x)
        a = jax.nn.silu(self._mm("bsd,df->bsf", h, w["ffn/w1"], mode, (0,)))
        a = a * self._mm("bsd,df->bsf", h, w["ffn/w3"], mode, (0,))
        return x + self._mm("bsf,fd->bsd", a, w["ffn/w2"], mode, (0,))

    def _head_fn(self, x, at, table, mode="f32"):
        """Logits at positions ``at`` (B, G) of final states x (B, S, d)."""
        h = self._norm(jnp.take_along_axis(x, at[..., None], axis=1))
        return self._mm("bgd,dv->bgv", h, self._head_weight(table), mode,
                        (0,))

    def _pool_fn(self, x, lengths):
        h = self._norm(x)
        mask = (jnp.arange(x.shape[1])[None, :] < lengths[:, None])
        e = (h * mask[..., None]).sum(1) / jnp.maximum(
            lengths[:, None].astype(F32), 1.0)
        return e / jnp.maximum(jnp.linalg.norm(e, axis=-1, keepdims=True),
                               1e-9)

    # ---------------------------------------------------------- entry points
    def hidden(self, tokens, mode="f32", table=None):
        """Final-layer states (B, S, d) of padded token rows (B, S)."""
        table = self.embed_table() if table is None else table
        x = jnp.take(table, jnp.asarray(tokens, jnp.int32), axis=0)
        for kr in self._layer_keys:
            x = self._layer(x, kr, mode=mode)
        return x

    def head(self, x, at, table, mode="f32"):
        """Logits (B, G, V) at positions ``at`` (B, G) of final states."""
        return self._head(x, jnp.asarray(at, jnp.int32), table, mode=mode)

    def logits_at(self, tokens, at, mode="f32"):
        """Logits (B, G, V) at positions ``at`` (B, G) of token rows."""
        table = self.embed_table()
        return self.head(self.hidden(tokens, mode, table), at, table, mode)

    def embed(self, tokens, lengths, mode="f32"):
        """Unit mean-pooled final states (B, d) of rows (B, S), each
        ``lengths[b]`` tokens long (the rest is padding after them)."""
        x = self.hidden(tokens, mode)
        return self._pool(x, jnp.asarray(lengths, jnp.int32))


def build(model: dict, seed: int, published: dict) -> DenseTransformer:
    """``published`` is read by no dense equation: these configurations
    cut no width, and their ``published`` only records departures."""
    return DenseTransformer(model, seed)
