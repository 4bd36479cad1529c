"""Smoke-size cuts of the benchmark's cells, for the CPU tests."""

import copy

# cells whose files are kept but which BENCHMARK.json does not run yet
# (PERF.md, Open questions): name -> (a cell of the same kind, config,
# traffic)
WAITING = {"phi-3-vision-4.2b.batch_map": ("olmo-1b.batch_map",
                                           "phi-3-vision-4.2b",
                                           "batch_map_16")}


def smoke_model(arch: str, model: dict) -> dict:
    """The configuration's ``model`` block at the program's smoke widths
    (the served model's own test preset), everything else kept."""
    from repro.configs import get_smoke_config

    c = get_smoke_config(arch)
    return dict(model, num_hidden_layers=c.num_layers, hidden_size=c.d_model,
                num_attention_heads=c.num_heads,
                num_key_value_heads=c.num_kv_heads,
                head_dim=c.resolved_head_dim, intermediate_size=c.d_ff,
                vocab_size=c.vocab_size)


def make_smoke(cell: dict, rows: int = 3, corpus_rows: int = 2048,
               max_context: int = 1024) -> dict:
    """A cell as the benchmark loads it, cut to a size the CPU runs in
    seconds: smoke widths, few rows, a small corpus.  Limits are kept."""
    cell = copy.deepcopy(cell)
    conf, traffic = cell["config"], cell["traffic"]
    conf["smoke"] = True
    conf["max_context"] = max_context
    conf["model"] = smoke_model(conf["arch"], conf["model"])
    traffic["rows_per_plan"] = rows
    if "corpus" in traffic:
        traffic["corpus"]["rows"] = corpus_rows
        traffic["sample"]["queries"] = 4
    return cell


def cell_from_files(cell: dict, config: str, traffic: str) -> dict:
    """``cell`` with its configuration and traffic read from the named
    files instead: for a pair of files no cell of BENCHMARK.json runs
    yet (kept for a cell that waits for a program fix)."""
    import json

    cell = copy.deepcopy(cell)
    root = cell["root"] / "bench"
    like = cell["config"]["limits"]
    cell["config"] = json.loads((root / "configs" / f"{config}.json"
                                 ).read_text())
    # limits not yet read on the chip: the smoke run borrows the others'
    for k, v in cell["config"]["limits"].items():
        if v is None:
            cell["config"]["limits"][k] = like[k]
    cell["traffic"] = json.loads((root / "traffic" / f"{traffic}.json"
                                  ).read_text())
    return cell
