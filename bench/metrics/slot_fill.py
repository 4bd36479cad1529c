"""Share of the serving engine's decode slots that held a request, in
percent: the active slots of every decode step of the window (the
program's counter ``engine.slot_steps``) over its decode steps
(``engine.decode`` spans) times the engine's slots.  A slot left empty
is a request that could have shared the step's weight reads."""


def read(rec):
    tel = rec.get("telemetry")
    if not tel or not rec.get("n_slots"):
        return None
    steps = tel.get("engine.decode", {}).get("n", 0)
    if not steps:
        return None
    active = tel.get("engine.slot_steps", {}).get("n", 0)
    return 100.0 * active / (steps * rec["n_slots"])
