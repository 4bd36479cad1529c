"""The one traffic generator: turns a traffic file and ``--seed`` into
the rows, corpus and plans a run sends.

A traffic file (``bench/traffic/<name>.json``) holds only parameters:

    rows_per_plan   input rows of each plan
    text_col/label  the column the rows fill, and a short tag that starts
                    each row (with the row's number, so no two rows of a
                    run are alike and neither dedup nor the prediction
                    cache can serve one)
    row_bytes       length distribution in bytes: lognormal (median,
                    sigma) or uniform, clipped to [min, max]
    plan            the operators, in order: llm_complete (whose answers
                    the correctness check can follow row by row; an
                    operator's own ``max_output_tokens`` overrides the
                    traffic's) and vector_topk
    max_output_tokens, corpus (rows, col, label, row_bytes), warmup
                    (plans of all-"min" or all-"max" rows run in set-up,
                    so every shape the window can meet is compiled),
                    sample and limits (read by the correctness check)

Plan ``i`` of every seed has the same row lengths in the same order
(drawn from ``i`` alone); the seed picks their words.  The planner packs
rows into requests, and the provider splits requests that overflow, by
length alone, so every seed sends the same requests of the same sizes,
and differs only in what they say.
"""

from __future__ import annotations

import numpy as np

WORDS = (
    "the app crashed when I opened my statement after the update and the "
    "transfer failed twice login is slow card was declined at checkout "
    "support never answered refund arrived late great service but fees "
    "are high search returns nothing useful dark mode looks broken on my "
    "phone notifications arrive hours late export to csv drops rows the "
    "join query timed out index rebuild took all night storage grew fast "
    "vector search finds near duplicates columnar scans are quick budget "
    "report totals look wrong latency spikes every morning backups ran "
    "fine password reset link expired dashboard charts load slowly"
).split()


def _rng(*parts) -> np.random.Generator:
    return np.random.default_rng([abs(int(p)) for p in parts])


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    elif spec["dist"] == "uniform":
        x = rng.uniform(lo, hi + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(int)


def texts(label: str, first_id: int, lens, rng: np.random.Generator):
    """One text per length: ``<label><id> `` then random words, cut to
    exactly that many bytes."""
    stream = " ".join(np.asarray(WORDS)[rng.integers(
        0, len(WORDS), min(24 * len(lens) + 64, 200_000))])
    offs = rng.integers(0, len(stream) - 256, len(lens))
    out = []
    for j, (n, o) in enumerate(zip(lens, offs)):
        head = f"{label}{first_id + j} "
        out.append((head + stream[o:o + max(int(n) - len(head), 0)])[:max(
            int(n), len(head))])
    return out


def plan_rows(traffic: dict, seed: int, index: int) -> list[str]:
    """Rows of plan ``index`` of a run: lengths, in order, fixed by
    ``index``; words by ``seed``."""
    n = int(traffic["rows_per_plan"])
    lens = lengths(traffic["row_bytes"], n, _rng(index, 7919))
    return texts(traffic["label"], index * n, lens, _rng(seed, index, 1))


def warm_rows(traffic: dict, which: str, seed: int) -> list[str]:
    n = int(traffic["rows_per_plan"])
    lens = [int(traffic["row_bytes"][which])] * n
    return texts("w" + traffic["label"], 0, lens, _rng(seed, 2, len(which)))


def corpus_texts(traffic: dict, seed: int) -> list[str]:
    c = traffic["corpus"]
    rng = _rng(seed, 3)
    return texts(c["label"], 0, lengths(c["row_bytes"], int(c["rows"]), rng),
                 rng)


def corpus_vectors(seed: int, rows: int, dim: int):
    """(rows, dim) float32 standard normal embedding rows, drawn on the
    device in one call from ``seed``."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) % 2**32), 17)
    return jax.jit(lambda k: jax.random.normal(k, (rows, dim), jnp.float32)
                   )(key)


def model_specs(config: dict, traffic: dict) -> dict:
    name, window = config["name"], int(config["max_context"])
    return {"gen": {"model": f"{name}-local", "context_window": window,
                    "max_output_tokens": int(traffic.get(
                        "max_output_tokens", 4))},
            "emb": {"model": f"{name}-embed", "context_window": window}}


def build_plan(ctx, traffic: dict, rows: list[str], models: dict,
               corpus=None):
    """The plan over one batch of rows, as a user would write it."""
    from repro.engine import Pipeline, Table

    pipe = Pipeline(ctx, Table({traffic["text_col"]: list(rows)}),
                    traffic["text_col"] + "s")
    for op in traffic["plan"]:
        kind = op["op"]
        if kind == "llm_complete":
            gen = dict(models["gen"], **{k: int(op[k]) for k in
                                         ("max_output_tokens",) if k in op})
            pipe = pipe.llm_complete(op["out"], gen,
                                     {"prompt": op["prompt"]}, op["cols"])
        elif kind == "vector_topk":
            pipe = pipe.vector_topk(op["out"], models["emb"],
                                    op["query_col"], corpus, k=int(op["k"]),
                                    doc_col=op["doc_col"])
        else:
            raise ValueError(f"the generator has no operator {kind!r}")
    return pipe
