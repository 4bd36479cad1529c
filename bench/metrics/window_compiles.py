"""Backend compilations inside the measured window: the program's
``compile.<function>`` counters summed (``repro.core.telemetry``).  Set-up
warms every shape the window meets, so anything above 0 is a shape or a
program that compiled while it was timed."""


def read(rec):
    tel = rec.get("telemetry")
    if not tel:
        return None
    return sum(v["n"] for k, v in tel.items() if k.startswith("compile."))
