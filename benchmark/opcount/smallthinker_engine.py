"""Parameters, cache bytes, and the operations and bytes a decode step, a
prefill bucket and the prefill's attention kernel need, from the
configuration's shapes and the program's counters (family
``smallthinker_engine``).

"Needs" is the algorithm's floor, not what a program happens to move: every
weight that takes part is read once — of the experts those that were hit,
not all that are held — and K and V are read for the rows the slots hold:
``rows_full`` a full layer, ``rows_ring`` (each slot's rows capped at the
window) a ring.  A prefill's attention is counted with its window: a query
at ``p`` reads ``min(p + 1, window)`` rows of a window layer."""

from benchmark.reference import smallthinker_engine as ref

_BYTES = {"float32": 4, "bfloat16": 2}


def parameters(config):
    """Parameter counts by part (one layer's where layers repeat)."""
    z = ref.sizes(config)
    e, hd = z["embed"], z["head_dim"]
    return {
        "attention": 2 * e * z["heads"] * hd + 2 * e * z["kv_heads"] * hd,
        "expert": 3 * e * z["expert_ffn"],
        "router": e * z["num_experts"],
        "embed": z["vocab"] * e, "head": e * z["vocab"],
        "norms_layer": 2 * e, "ln_f": e}


def held_parameters(config):
    """Every parameter this chip holds."""
    z, p = ref.sizes(config), parameters(config)
    return (z["layers"] * (p["attention"] + p["norms_layer"] + p["router"]
                           + z["experts_held"] * p["expert"])
            + p["embed"] + p["head"] + p["ln_f"])


def kinds(config):
    """(full layers, window layers) among those held."""
    z = ref.sizes(config)
    rings = sum(z["window_layout"])
    return z["layers"] - rings, rings


def row_bytes(config):
    """K and V of one row of one layer."""
    z = ref.sizes(config)
    return 2 * z["kv_heads"] * z["head_dim"] \
        * _BYTES[config["precision"]["kv_cache"]]


def cache_bytes(config, slots):
    """K and V of the whole cache: ``max_len`` rows a slot in a full
    layer, ``window`` in a ring."""
    z = ref.sizes(config)
    full, rings = kinds(config)
    return slots * row_bytes(config) * (full * z["max_len"]
                                        + rings * z["window"])


def attention_bytes(config, rows_full, rows_ring):
    """K and V of the rows the layers read in one step."""
    full, rings = kinds(config)
    return row_bytes(config) * (full * rows_full + rings * rows_ring)


def _weight_bytes(config, experts_hit):
    """Every weight a pass over all layers reads once, ``experts_hit`` of
    the experts (summed over the layers); router matrices and gains are
    float32; of the embedding only the rows' own lines, left out."""
    z, p = ref.sizes(config), parameters(config)
    wb = _BYTES[config["precision"]["weights"]]
    return wb * (z["layers"] * p["attention"] + experts_hit * p["expert"]
                 + p["head"]) \
        + 4 * (z["layers"] * (p["router"] + p["norms_layer"]) + p["ln_f"])


def step_bytes(config, experts_hit, rows_full, rows_ring):
    """Bytes one decode step has to read.  ``experts_hit``: experts that
    took part, summed over the layers; ``rows_full`` / ``rows_ring``: the
    rows the live slots hold in a full layer and in a ring."""
    return _weight_bytes(config, experts_hit) \
        + attention_bytes(config, rows_full, rows_ring)


def step_flops(config, rows, local_picks, rows_full, rows_ring):
    """Operations of one decode step over ``rows`` rows: two a parameter
    and row for what every row passes (attention's projections, the router,
    the head), two a parameter for each of the ``local_picks`` (row, held
    expert) pairs, and the scores and weighted sums over the rows held."""
    z, p = ref.sizes(config), parameters(config)
    full, rings = kinds(config)
    every_row = z["layers"] * (p["attention"] + p["router"]) + p["head"]
    return 2 * rows * every_row + 2 * local_picks * p["expert"] \
        + 4 * z["heads"] * z["head_dim"] \
        * (full * rows_full + rings * rows_ring)


def _pairs(n, window=None):
    """(query, key) pairs of causal attention over ``n`` positions, a query
    at ``p`` reading ``min(p + 1, window)`` keys."""
    if window is None or window >= n:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def flash_flops(config, bucket, window):
    """Operations of one call of the prompt's attention kernel over a
    bucket: scores and weighted sums, two operations a multiply-add."""
    z = ref.sizes(config)
    return 4 * z["heads"] * z["head_dim"] * _pairs(bucket, window)


def flash_bytes(config, bucket):
    """Bytes of one call: Q and the context once, K and V once."""
    z = ref.sizes(config)
    wb = _BYTES[config["precision"]["weights"]]
    return wb * bucket * z["head_dim"] * 2 * (z["heads"] + z["kv_heads"])


def prefill_flops(config, bucket):
    """Operations of one prefill of a bucket: every layer over every
    position (projections, router, ``top_k`` experts a row when all are
    held, attention with its window), the head for one row."""
    z, p = ref.sizes(config), parameters(config)
    full, rings = kinds(config)
    local = z["top_k"] * z["experts_held"] / z["num_experts"]
    return 2 * bucket * z["layers"] * (p["attention"] + p["router"]
                                       + local * p["expert"]) \
        + full * flash_flops(config, bucket, None) \
        + rings * flash_flops(config, bucket, z["window"]) \
        + 2 * p["head"]


def prefill_bytes(config, bucket):
    """Bytes of one prefill: every weight once (a prompt's rows hit every
    expert), K and V of the bucket written."""
    z = ref.sizes(config)
    full, rings = kinds(config)
    return _weight_bytes(config, z["layers"] * z["experts_held"]) \
        + row_bytes(config) * (full * bucket
                               + rings * min(bucket, z["window"]))
