"""Continuous-batching serving engine (the runtime behind LocalJaxProvider).

Design (vLLM-style, adapted to JAX static shapes):
  * a fixed number of decode SLOTS; each slot owns one row of the batched
    cache pytree (B = n_slots);
  * prompts enter through CHUNKED PREFILL (prefill_chunk, Sarathi-style):
    whole chunks of ``chunk`` tokens, remainder token-by-token through the
    decode step — exact for attention AND recurrent archs, and only two
    compiled shapes per model;
  * every engine step decodes all active slots at their own positions
    (per-row ``pos`` vectors);
  * finished requests free their slot; waiting requests are admitted FCFS.

On CPU this runs the same jitted step functions the TPU mesh would run
(minus the sharding policy), so scheduler behaviour, cache management and
sampling are exercised end-to-end.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.core.batching import ContextOverflowError
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.serving.steps import make_embed_step


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 32
    eos_token: int = -1              # -1: never stop early
    generated: List[int] = field(default_factory=list)
    finished: bool = False
    slot: int = -1
    pos: int = 0                     # tokens of this request already cached
    pending_prompt: int = 0          # prompt tokens not yet prefetched


class ServingEngine:
    def __init__(self, cfg: ModelConfig, *, n_slots: int = 4,
                 max_context: int = 2048, chunk: int = 32,
                 checkpoint: Optional[str] = None, seed: int = 0):
        self.cfg = cfg.replace(remat=False)
        self.n_slots = n_slots
        self.max_context = max_context
        self.chunk = chunk
        if checkpoint:
            from repro.training.checkpoint import CheckpointManager
            mgr = CheckpointManager(checkpoint)
            self.params = mgr.restore_latest()["params"]
        else:
            self.params = M.init_params(self.cfg, jax.random.PRNGKey(seed))
        self.cache = M.init_cache(self.cfg, n_slots, max_context)
        self._rid = itertools.count()
        self.waiting: List[Request] = []
        self.active: List[Optional[Request]] = [None] * n_slots
        self.pos = np.zeros(n_slots, np.int32)
        self.cur_tok = np.zeros(n_slots, np.int32)
        self.steps = 0

        # named functions, so that compiles and the device trace's
        # modules say which step is which
        cfgc = self.cfg
        embed_step = make_embed_step(cfgc)

        def engine_decode(p, t, c, pos):
            return M.decode_step(cfgc, p, t, c, pos)

        def engine_prefill(p, t, c, off):
            return M.prefill_chunk(cfgc, p, t, c, off)

        def engine_embed(p, batch):
            return embed_step(p, batch)

        self._decode = jax.jit(engine_decode)
        self._extend = jax.jit(engine_prefill)
        self._embed = jax.jit(engine_embed)

    # ------------------------------------------------------------------ API
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               eos_token: int = -1) -> Request:
        """Queue a request.  One whose prompt plus ``max_new_tokens``
        cannot fit ``max_context`` raises ``ContextOverflowError`` here:
        the caller splits or drops it, the engine never answers it
        with nothing."""
        need = len(prompt) + max_new_tokens
        if need > self.max_context:
            raise ContextOverflowError(
                f"{len(prompt)} prompt + {max_new_tokens} new tokens > "
                f"max_context {self.max_context}")
        req = Request(rid=next(self._rid), prompt=list(prompt),
                      max_new_tokens=max_new_tokens, eos_token=eos_token)
        req.pending_prompt = len(req.prompt)
        self.waiting.append(req)
        return req

    def generate(self, prompt: Sequence[int], max_new_tokens: int = 32,
                 eos_token: int = -1) -> List[int]:
        req = self.submit(prompt, max_new_tokens, eos_token)
        while not req.finished:
            self.step()
        return req.generated

    def run_until_idle(self, max_steps: int = 100_000):
        while (self.waiting or any(self.active)) and max_steps:
            self.step()
            max_steps -= 1

    def score(self, tokens: Sequence[int]) -> np.ndarray:
        """Teacher-forced logits ``(len(tokens), vocab_size)`` of one
        prompt through the engine's own compiled steps, split as a request
        is: whole chunks through chunked prefill, the last ``<= chunk``
        tokens one by one through the decode step.  Runs in slot 0 of a
        fresh cache; the serving state is untouched."""
        if len(tokens) > self.max_context:
            raise ContextOverflowError(
                f"{len(tokens)} tokens > max_context {self.max_context}")
        cache = M.init_cache(self.cfg, self.n_slots, self.max_context)
        pos = np.zeros(self.n_slots, np.int32)
        n_full = max(len(tokens) - 1, 0) // self.chunk
        out = []
        for c in range(n_full):
            toks = np.zeros((self.n_slots, self.chunk), np.int32)
            toks[0] = tokens[c * self.chunk:(c + 1) * self.chunk]
            logits, cache = self._extend(self.params, jnp.asarray(toks),
                                         cache, jnp.asarray(pos))
            out.append(np.asarray(logits[0]))
            pos[0] += self.chunk
        for t in tokens[n_full * self.chunk:]:
            toks = np.zeros((self.n_slots, 1), np.int32)
            toks[0, 0] = t
            logits, cache = self._decode(self.params, jnp.asarray(toks),
                                         cache, jnp.asarray(pos))
            out.append(np.asarray(logits[0]))
            pos[0] += 1
        return np.concatenate(out)[:, :self.cfg.vocab_size]

    # ----------------------------------------------------------------- step
    def _admit(self):
        for slot in range(self.n_slots):
            if self.active[slot] is None and self.waiting:
                req = self.waiting.pop(0)
                req.slot = slot
                req.pos = 0
                self.active[slot] = req

    def _prefill_work(self):
        """Advance chunked prefill for slots still consuming their prompt."""
        for slot, req in enumerate(self.active):
            # keep >=1 prompt token for the decode path so the first
            # generated token comes from real last-token logits
            if req is None or req.pending_prompt <= self.chunk:
                continue
            # process one full chunk for this slot (other slots no-op via
            # a masked chunk of repeated pad? -> simpler: per-slot call on a
            # batch where only this slot's chunk is real; positions of the
            # other slots point at their current pos so their cache rows
            # are overwritten with identical values (harmless: we reuse the
            # current token, and the masked write targets the same cells).
            with telemetry.span("engine.prefill"):
                start = len(req.prompt) - req.pending_prompt
                chunk_toks = req.prompt[start:start + self.chunk]
                toks = np.zeros((self.n_slots, self.chunk), np.int32)
                toks[slot] = chunk_toks
                # rows without work: point their writes at their own
                # positions (re-writing the same K/V values they hold)
                offs = np.array(self.pos, np.int32)
                logits, new_cache = self._extend(
                    self.params, jnp.asarray(toks), self.cache,
                    jnp.asarray(offs))
                # merge: keep new cache rows only for the working slot
                self.cache = engine_merge_row(self.cache, new_cache,
                                              np.int32(slot))
                req.pos += self.chunk
                self.pos[slot] += self.chunk
                req.pending_prompt -= self.chunk
            return True      # one chunk per engine step keeps latency fair
        return False

    def step(self):
        with telemetry.span("engine.admit"):
            self._admit()
        self.steps += 1
        if self._prefill_work():
            return
        # build the decode batch: remaining prompt tokens are fed one at a
        # time (teacher forcing); slots past their prompt sample greedily
        n_active = 0
        toks = np.zeros((self.n_slots, 1), np.int32)
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            n_active += 1
            if req.pending_prompt > 0:
                idx = len(req.prompt) - req.pending_prompt
                toks[slot, 0] = req.prompt[idx]
            else:
                toks[slot, 0] = self.cur_tok[slot]
        if not n_active:
            return
        with telemetry.span("engine.decode"):
            pos_vec = jnp.asarray(self.pos, jnp.int32)
            logits, self.cache = self._decode(
                self.params, jnp.asarray(toks), self.cache, pos_vec)
        telemetry.count("engine.slot_steps", n_active)
        with telemetry.span("engine.sample"):
            nxt = np.asarray(jnp.argmax(
                _mask_vocab(self.cfg, logits[:, 0]), axis=-1), np.int32)
            self._advance(nxt)

    def _advance(self, nxt: np.ndarray):
        """Each active slot past one decode step: its sampled token."""
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            self.pos[slot] += 1
            req.pos += 1
            if req.pending_prompt > 0:
                req.pending_prompt -= 1
                if req.pending_prompt == 0:
                    self.cur_tok[slot] = nxt[slot]
                    req.generated.append(int(nxt[slot]))
            else:
                self.cur_tok[slot] = nxt[slot]
                req.generated.append(int(nxt[slot]))
            done = (len(req.generated) >= req.max_new_tokens
                    or (req.eos_token >= 0 and req.generated
                        and req.generated[-1] == req.eos_token)
                    or req.pos >= self.max_context - 1)
            if done and req.pending_prompt == 0:
                req.finished = True
                self.active[slot] = None
                self.pos[slot] = 0
                self.cur_tok[slot] = 0

    # ---------------------------------------------------------------- embed
    def embed(self, tokens: Sequence[int]) -> np.ndarray:
        """Mean-pooled hidden state (llm_embedding backend); bucketed jit.

        Padding uses token id -1: the embedding lookup clips it to 0 but the
        pooling mask inside the embed step (tokens >= 0) excludes it.
        """
        return self.embed_batch([tokens])[0]

    def embed_batch(self, token_lists) -> np.ndarray:
        """One padded forward for N texts — the 48x-style batching lever."""
        with telemetry.span("engine.embed"):
            longest = max((len(t) for t in token_lists), default=1)
            L = 1 << max(5, (max(longest, 1) - 1).bit_length())
            toks = np.full((len(token_lists), L), -1, np.int32)
            for i, t in enumerate(token_lists):
                toks[i, :len(t)] = t
            emb = self._embed(self.params, {"tokens": jnp.asarray(toks)})
            return np.asarray(emb)


def _mask_vocab(cfg, logits):
    if cfg.padded_vocab != cfg.vocab_size:
        mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        return jnp.where(mask, logits, -jnp.inf)
    return logits


@jax.jit
def engine_merge_row(old_tree, new_tree, row):
    """Take row ``row`` (batch dim = axis 1 under the stacked-layer axis 0)
    from new_tree, everything else from old_tree."""
    def merge(o, n):
        sel = jnp.arange(o.shape[1]) == row
        shape = [1, o.shape[1]] + [1] * (o.ndim - 2)
        return jnp.where(sel.reshape(shape), n, o)
    return jax.tree.map(merge, old_tree, new_tree)
