"""Set-up seconds: process start to the end of the warm-up plans
(imports, weights, corpus and index, every compile the window needs)."""


def read(rec):
    return rec["setup_s"]
