"""Share of provider attempts that overflowed the context and were split
and retried, in percent, from the session's execution reports (whose
``requests`` counts the attempts that succeeded, ``retries`` the ones
that overflowed)."""


def read(rec):
    attempts = rec["requests"] + rec["retries"]
    if not attempts:
        return None
    return 100.0 * rec["retries"] / attempts
