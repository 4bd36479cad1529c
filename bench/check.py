"""The comparisons that decide a run's ``correct``.

Each returns plain numbers; ``harness`` sets them beside their limits.
Nothing here imports the system under test.  The reference model is the
configuration's own plain reference (``bench/reference``), with weights
drawn from the seed; the served tokens, answers, query embeddings and
candidate rows come from what the run recorded.

Generation (``logit_gap``): a sample of the window's finished requests,
drawn from the seed and holding the longest, is run through the
reference once over prompt + served tokens.  For each served token the
number is how far its reference logit lies below the reference's best
at that position; the widest gap over the sample is compared.  Greedy
decoding in bfloat16 only ever picks a near-best token, while a wrong
cache slot, position or token lands far below the best.

Rows (``rows_wrong``): every output row of every plan in the window
against the request that carried it to the engine.  A row is found in
the prompt of exactly one served request per map (its text is unique),
and its answer must quote what that request served.

Retrieval (``embed_dist``, ``scan_gap``): a sample of the window's
queries.  The served query embedding against the reference's (the L2
distance of the unit vectors), and each served candidate row against an
exact float32 scan of the reference's normalised corpus with the served
query embedding: the gap between its served and reference score, or by
how far its reference score lies below the k-th best, whichever is
larger.  A missing or repeated candidate counts as an infinite gap.

The control (``bench/control.py``, never run by the benchmark's own
runs) puts the reference one precision below the configuration's in the
program's place and reads the same numbers through the same functions
(``control=True``); the limits lie between the program's readings and
the control's.
"""

from __future__ import annotations

import ast

import numpy as np

GEN_CAP = 64        # the provider serves at most 64 tokens per request
ANSWER_BYTES = 32   # and answers each row with the first 32 bytes of them


def byte_tokens(text: str) -> list[int]:
    return list(text.encode())


def pad_rows(rows, length: int) -> np.ndarray:
    """Token lists to one (B, length) int32 array padded with 0 at the end;
    causal attention keeps the padding out of every real position."""
    out = np.zeros((len(rows), length), np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out


# --------------------------------------------------------------- generation
def sample_requests(requests, seed: int, max_sequences: int):
    """(prompt, served) token lists of finished requests: the longest,
    then others in an order drawn from the seed, at most
    ``max_sequences`` of them."""
    done = [(list(r.prompt), list(r.generated)) for r in requests
            if r.finished and r.generated]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: len(done[i][0]) + len(done[i][1]))
    order = [longest] + [int(i) for i in np.random.default_rng(
        [abs(int(seed)), 5]).permutation(len(done)) if i != longest]
    return [done[i] for i in order[:max_sequences]]


def _teacher_forced(seqs, rows_: int, pad_to: int):
    """Token rows (rows_, pad_to), served-token positions (rows_, G), the
    served tokens (rows_, G) and a validity mask, at fixed shapes."""
    G = max(GEN_CAP, -(-max(len(g) for _, g in seqs) // GEN_CAP) * GEN_CAP)
    rows = [p + g[:-1] for p, g in seqs]
    rows += [[0]] * (rows_ - len(rows))
    at = np.zeros((rows_, G), np.int32)
    tok = np.zeros((rows_, G), np.int32)
    valid = np.zeros((rows_, G), bool)
    for b, (p, g) in enumerate(seqs):
        at[b, :len(g)] = len(p) - 1 + np.arange(len(g))
        tok[b, :len(g)] = g
        valid[b, :len(g)] = True
    return pad_rows(rows, pad_to), at, tok, valid


def _gaps(ref_logits, tok, valid):
    ref = np.asarray(ref_logits, np.float64)
    best = ref.max(-1)
    got = np.take_along_axis(ref, tok[..., None], -1)[..., 0]
    return float(np.max(np.where(valid, best - got, -np.inf)))


def logit_gap(ref, seqs, pad_to: int, control: bool = False,
              block: int = 8) -> float:
    """Widest gap of a served token's reference logit below the best,
    with the reference run over ``block`` sequences at a time.  With
    ``control`` the int8 reference stands in for the program: at each
    served position of the same prompts and served tokens, the token it
    puts first is read in the served token's place."""
    table = ref.embed_table()
    worst = -np.inf
    for lo in range(0, len(seqs), block):
        rows, at, tok, valid = _teacher_forced(seqs[lo:lo + block], block,
                                               pad_to)
        logits = ref.head(ref.hidden(rows, "f32", table), at, table, "f32")
        if control:
            tok = np.asarray(ref.head(ref.hidden(rows, "int8", table), at,
                                      table, "int8")).argmax(-1)
        worst = max(worst, _gaps(logits, tok, valid))
    return worst


# --------------------------------------------------------------------- rows
def served_text(tokens) -> str:
    """The text of served byte tokens, as the provider turns them back
    into text (one byte a token, latin-1)."""
    return bytes(int(t) % 256 for t in tokens).decode("latin1")


def _answer(value):
    """A row's answer cell as the text it quotes, or None."""
    try:
        out = ast.literal_eval(value) if isinstance(value, str) else None
    except (ValueError, SyntaxError):
        return None
    return out if isinstance(out, str) else None


def rows_wrong(traffic: dict, plans, requests) -> int:
    """Output rows or cells of the window's map plans that are not the
    rows sent, or not answered by the one request that carried them.

    For each map of the plan, a row must lie in exactly one request that
    reached the engine (its prompt holds the map's prompt and the row's
    text, which is unique in the run), and its answer cell must quote the
    first ``ANSWER_BYTES`` of what that request served: the local
    provider answers every row of a request with the request's text."""
    col = traffic["text_col"]
    maps = [op for op in traffic["plan"] if op["op"] == "llm_complete"]
    done = [(served_text(r.prompt), served_text(r.generated))
            for r in requests if r.finished]
    bad = 0
    for rows, table in plans:
        got_rows = list(table.column(col))
        if got_rows != list(rows):
            bad += abs(len(got_rows) - len(rows)) + sum(
                a != b for a, b in zip(got_rows, rows))
            continue
        for op in maps:
            carriers = [(p, g) for p, g in done if op["prompt"] in p]
            for r, cell in zip(rows, table.column(op["out"])):
                served = [g for p, g in carriers if r in p]
                bad += (len(served) != 1
                        or _answer(cell) != served[0][:ANSWER_BYTES])
    return bad


def retrieval_rows_wrong(traffic: dict, plans, doc_id: dict) -> int:
    """Output rows of the window's retrieval plans whose shape is wrong:
    not k rows per query in query order, or a document not in the
    corpus."""
    op = next(o for o in traffic["plan"] if o["op"] == "vector_topk")
    k, qcol, dcol = int(op["k"]), op["query_col"], op["doc_col"]
    bad = 0
    for rows, table in plans:
        want = [q for q in rows for _ in range(k)]
        got = list(table.column(qcol))
        bad += abs(len(got) - len(want)) + sum(a != b for a, b in
                                                 zip(got, want))
        bad += sum(d not in doc_id for d in table.column(dcol))
    return bad


# ---------------------------------------------------------------- retrieval
def sample_queries(plans, seed: int, n: int):
    """(plan index, row index) of up to ``n`` queries of the window,
    drawn from the seed, with the longest among them."""
    cands = [(p, j) for p, (rows, _) in enumerate(plans)
             for j in range(len(rows))]
    longest = max(cands, key=lambda c: len(plans[c[0]][0][c[1]]))
    rest = [cands[i] for i in np.random.default_rng(
        [abs(int(seed)), 11]).permutation(len(cands))
        if cands[i] != longest]
    return [longest] + rest[:n - 1]


def embed_rows(texts, pad_to: int):
    toks = [byte_tokens(t) for t in texts]
    return pad_rows(toks, pad_to), np.asarray([len(t) for t in toks])


def embed_dist(served, ref_vecs) -> float:
    """Widest L2 distance between served and reference unit vectors."""
    d = np.asarray(served, np.float64) - np.asarray(ref_vecs, np.float64)
    return float(np.sqrt((d * d).sum(-1)).max())


def scan_gap(ref_scores, served_ids, served_scores, k: int) -> float:
    """ref_scores (Q, N) exact float32 scores of the served query
    embeddings against the reference's normalised corpus; served_ids and
    served_scores (Q, k) the plan's candidates."""
    ref = np.asarray(ref_scores, np.float64)
    kth = -np.sort(-ref, axis=1)[:, k - 1]
    worst = 0.0
    for q in range(ref.shape[0]):
        ids = [int(i) for i in served_ids[q]]
        if len(ids) != k or len(set(ids)) != k or min(ids) < 0:
            return float("inf")
        s_ref = ref[q, ids]
        worst = max(worst,
                    float(np.abs(np.asarray(served_scores[q]) - s_ref).max()),
                    float((kth[q] - s_ref).max()))
    return worst
