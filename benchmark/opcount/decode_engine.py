"""Parameters, cache bytes, and the operations and bytes one decode step
needs, from the configuration's shapes alone (family ``decode_engine``).

"Needs" is the algorithm's floor, not what a program happens to move: every
weight that takes part in a step is read once, and K and V are read for the
tokens that are live, not for the rows a cache reserves."""


def _dims(config):
    return (int(config["assumed"]["vocab_padded"]), int(config["n_embd"]),
            int(config["n_layer"]), int(config["n_inner"]),
            int(config["n_positions"]))


def parameters(config):
    """Parameter counts by part."""
    vocab, embed, layers, ffn, max_len = _dims(config)
    block = embed * 3 * embed + embed * embed + 2 * embed * ffn + 2 * embed
    return {"blocks": layers * block, "embed": vocab * embed,
            "head": embed * vocab, "pos": max_len * embed, "ln_f": embed}


def bytes_per_value(config):
    return {"float32": 4, "bfloat16": 2}[config["precision"]["kv_cache"]]


def cache_bytes_per_slot(config):
    """K and V of one slot's dense cache row: every layer, ``max_len``
    positions."""
    _vocab, embed, layers, _ffn, max_len = _dims(config)
    return 2 * layers * max_len * embed * bytes_per_value(config)


def step_bytes(config, live_tokens):
    """Bytes one decode step has to read: the blocks' weights, the final
    norm and the head once (of the embedding and position tables only the
    slots' own rows, left out), plus K and V of ``live_tokens`` tokens in
    every layer."""
    _vocab, embed, layers, _ffn, _max_len = _dims(config)
    p = parameters(config)
    weights = (p["blocks"] + p["head"] + p["ln_f"]) \
        * {"float32": 4, "bfloat16": 2}[config["precision"]["weights"]]
    return weights + 2 * layers * live_tokens * embed \
        * bytes_per_value(config)


def step_flops(config, slots, live_tokens):
    """Operations of one decode step over ``slots`` rows: two per
    parameter of the blocks and the head per row, plus the scores and the
    weighted sum over the live tokens."""
    _vocab, embed, layers, _ffn, _max_len = _dims(config)
    p = parameters(config)
    return 2 * slots * (p["blocks"] + p["head"]) \
        + 4 * layers * live_tokens * embed
