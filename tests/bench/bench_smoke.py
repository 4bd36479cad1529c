"""Smoke-size cuts of the benchmark's cells, for the CPU tests."""

import copy

# cells whose files are kept but which BENCHMARK.json does not run yet
# (PERF.md, Open questions): name -> (a cell of the same kind, config,
# traffic)
WAITING = {"phi-3-vision-4.2b.batch_map": ("olmo-1b.batch_map",
                                           "phi-3-vision-4.2b",
                                           "batch_map_16")}


# widths of a block that is not dense, which the dense smoke keys leave
# at their published size: a configuration that holds one cuts it itself
CUT_BY_THE_CONFIGURATION = (
    "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "moe_intermediate_size", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "first_k_dense_replace")


def smoke_config(conf: dict) -> dict:
    """The configuration at the program's smoke widths (the served
    model's own test preset): the dense keys from the preset, ``head_dim``
    only where the block has one, then the file's own ``smoke_cut`` over
    ``model`` and ``published``.  Raises ``ValueError`` naming a width
    of the block, or of ``published``, that neither sets."""
    from repro.configs import get_smoke_config

    c = get_smoke_config(conf["arch"])
    model = dict(conf["model"], num_hidden_layers=c.num_layers,
                 hidden_size=c.d_model, num_attention_heads=c.num_heads,
                 num_key_value_heads=c.num_kv_heads,
                 intermediate_size=c.d_ff, vocab_size=c.vocab_size)
    if "head_dim" in model:
        model["head_dim"] = c.resolved_head_dim
    cut = conf.get("smoke_cut", {})
    for block in ("model", "published"):
        left = [k for k in CUT_BY_THE_CONFIGURATION
                if conf.get(block, {}).get(k) is not None
                and k not in cut.get(block, {})]
        if left:
            raise ValueError(f"{conf.get('name')}: {block}.{left[0]} is a "
                             f"width the smoke preset does not set; give it "
                             f"under smoke_cut.{block}")
    out = dict(conf, model=dict(model, **cut.get("model", {})))
    if "published" in conf:
        out["published"] = dict(conf["published"],
                                **cut.get("published", {}))
    return out


def make_smoke(cell: dict, rows: int = 3, corpus_rows: int = 2048,
               max_context: int = 1024) -> dict:
    """A cell as the benchmark loads it, cut to a size the CPU runs in
    seconds: smoke widths, few rows, a small corpus.  Limits are kept."""
    cell = copy.deepcopy(cell)
    cell["config"] = conf = smoke_config(cell["config"])
    traffic = cell["traffic"]
    conf["smoke"] = True
    conf["max_context"] = max_context
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
