"""Serving-engine steps per input row (the engine's step counter)."""


def read(rec):
    if not rec["engine_steps"] or not rec["rows"]:
        return None
    return rec["engine_steps"] / rec["rows"]
