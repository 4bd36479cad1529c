"""Device busy milliseconds per engine step: the union of the device's
operation intervals in the traced window over the engine steps in it."""


def read(rec):
    tr = rec["trace"]
    if tr is None or not tr["busy_s"] or not rec["engine_steps"]:
        return None
    return 1000.0 * tr["busy_s"] / rec["engine_steps"]
