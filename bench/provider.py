"""The served provider, as the benchmark drives it.

``TracedProvider`` is ``LocalJaxProvider`` with its two entry points
wrapped: each call runs inside a ``jax.profiler.TraceAnnotation``
(``provider.complete`` / ``provider.embed``), so a traced run can say
what the host was doing while the device sat idle.  Each embedding
call's texts and vectors are kept for the correctness check, and the
engine's ``submit`` is wrapped to keep every generation request it is
given: the prompt tokens and the tokens it served.
"""

from __future__ import annotations

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.provider import LocalJaxProvider


class TracedProvider(LocalJaxProvider):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self.embeddings = []       # (texts, (n, d) float32 vectors)
        self.requests = []         # engine Request objects, in order
        submit = self.engine.submit

        def recording_submit(*a, **k):
            req = submit(*a, **k)
            self.requests.append(req)
            return req

        self.engine.submit = recording_submit

    def complete(self, model, mp, n_rows):
        with TraceAnnotation("provider.complete"):
            return super().complete(model, mp, n_rows)

    def embed(self, model, texts):
        with TraceAnnotation("provider.embed"):
            out = super().embed(model, texts)
        self.embeddings.append((list(texts), np.asarray(out, np.float32)))
        return out

    def forget(self):
        """Drop what was kept so far (the set-up's calls)."""
        self.embeddings, self.requests = [], []
