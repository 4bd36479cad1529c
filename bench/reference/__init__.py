"""Plain references of the configurations, one module per architecture.

A configuration names its module under ``"reference"``; the harness loads
``bench/reference/<name>.py`` by path and calls its

    build(model: dict, seed: int, published: dict) -> Reference

with the configuration's ``model`` block (the sizes served), the seed
the served model draws its weights from, and its ``published`` block
(``{}`` where there is none): the published value of every key the
configuration cut, such as all the routed experts a router scores where
a chip holds its share of them.  A module imports nothing of the system
under test; it writes out the layer equations and the recipe that turns
the seed into the served weights.

Tokens are (B, S) int32 rows, each from position 0 with padding after
it; ``mode`` is ``"f32"`` (every product at float32, ``HIGHEST``) or
``"int8"``, the lower-precision control.
"""

from __future__ import annotations

from typing import Protocol


class Reference(Protocol):
    def embed_table(self):
        """The (V, d) float32 token embedding."""

    def hidden(self, tokens, mode: str = "f32", table=None):
        """Final-layer states (B, S, d) of token rows, before the final
        norm; ``table`` is ``embed_table()``, drawn once by the caller."""

    def head(self, x, at, table, mode: str = "f32"):
        """Logits (B, G, V) at positions ``at`` (B, G) of final states."""

    def logits_at(self, tokens, at, mode: str = "f32"):
        """Logits (B, G, V) at positions ``at`` (B, G) of token rows."""

    def embed(self, tokens, lengths, mode: str = "f32"):
        """Unit mean-pooled final states (B, d) of rows, each
        ``lengths[b]`` tokens long."""

    def served_weights(self) -> dict:
        """``{path: float32 array}`` of every weight the program serves,
        keyed by the ``/``-joined keys of its parameter tree, stacked
        layer axis included: embedding, every stage, norms as the
        program stores them, the final norm, and the output head where
        it is untied.  For the tests, at smoke width."""
