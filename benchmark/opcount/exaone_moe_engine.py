"""Parameters, cache bytes, and the operations and bytes one decode step
needs, from the configuration's shapes and the program's routing counters
(family ``exaone_moe_engine``).

"Needs" is the algorithm's floor, not what a program happens to move: every
weight that takes part in a step is read once — of the routed experts those
that were hit, not all that are held — and K and V are read for the tokens
that are live, a window layer's capped at the window a session."""

from benchmark.reference import exaone_moe_engine as ref

_BYTES = {"float32": 4, "bfloat16": 2}


def parameters(config):
    """Parameter counts by part (one layer's where layers repeat)."""
    z = ref.sizes(config)
    e, hd = z["embed"], z["head_dim"]
    return {
        "attention": 2 * e * z["heads"] * hd + 2 * e * z["kv_heads"] * hd,
        "expert": 3 * e * z["expert_ffn"],
        "dense_mlp": 3 * e * z["dense_ffn"],
        "router": e * z["num_experts"] + z["num_experts"],
        "embed": z["vocab"] * e, "head": e * z["vocab"],
        "norms_layer": 2 * e + 2 * hd, "ln_f": e}


def held_parameters(config):
    """Every parameter this chip holds."""
    z, p = ref.sizes(config), parameters(config)
    sparse = z["mlp_types"].count("sparse")
    return (z["layers"] * (p["attention"] + p["norms_layer"])
            + (z["layers"] - sparse) * p["dense_mlp"]
            + sparse * (p["router"] + (1 + z["experts_held"]) * p["expert"])
            + p["embed"] + p["head"] + p["ln_f"])


def kinds(config):
    """(full layers, window layers, sparse layers) among those held."""
    z = ref.sizes(config)
    window = z["layer_types"].count("sliding_attention")
    return z["layers"] - window, window, z["mlp_types"].count("sparse")


def cache_bytes(config, slots):
    """K and V of the whole cache: ``max_len`` rows a slot in a full
    layer, ``window`` in a window layer."""
    z = ref.sizes(config)
    full, window, _ = kinds(config)
    row = 2 * z["kv_heads"] * z["head_dim"] \
        * _BYTES[config["precision"]["kv_cache"]]
    return slots * row * (full * z["max_len"] + window * z["window"])


def step_bytes(config, experts_hit, live_full, live_window):
    """Bytes one decode step has to read.  ``experts_hit``: routed experts
    that took part, summed over the sparse layers; ``live_full``: tokens
    the sessions hold; ``live_window``: the same with each session's count
    capped at the window.  Router matrices are float32; of the embedding
    only the rows' own lines, left out."""
    z, p = ref.sizes(config), parameters(config)
    full, window, sparse = kinds(config)
    wb = _BYTES[config["precision"]["weights"]]
    weights = wb * (z["layers"] * p["attention"]
                    + (z["layers"] - sparse) * p["dense_mlp"]
                    + (sparse + experts_hit) * p["expert"] + p["head"]) \
        + 4 * (sparse * p["router"] + z["layers"] * p["norms_layer"]
               + p["ln_f"])
    row = 2 * z["kv_heads"] * z["head_dim"] \
        * _BYTES[config["precision"]["kv_cache"]]
    return weights + row * (full * live_full + window * live_window)


def step_flops(config, rows, local_picks, live_full, live_window):
    """Operations of one decode step over ``rows`` rows: two a parameter
    and row for what every row passes (attention's projections, the dense
    MLP, the router, the shared expert, the head), two a parameter for
    each of the ``local_picks`` (row, held expert) pairs, and the scores
    and weighted sums over the live tokens."""
    z, p = ref.sizes(config), parameters(config)
    full, window, sparse = kinds(config)
    every_row = z["layers"] * p["attention"] \
        + (z["layers"] - sparse) * p["dense_mlp"] \
        + sparse * (p["router"] + p["expert"]) + p["head"]
    return 2 * rows * every_row + 2 * local_picks * p["expert"] \
        + 4 * z["heads"] * z["head_dim"] \
        * (full * live_full + window * live_window)
