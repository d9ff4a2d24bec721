"""Parameters, cache bytes, and the operations and bytes a pass (the decode
step of a model that generates by blocks), a prefill bucket and the
prefill's attention kernel need, from the configuration's shapes and the
program's counters (family ``sdar_engine``).

"Needs" is the algorithm's floor, not what a program happens to move: every
weight that takes part is read once — of the experts those that were hit,
not all that are held — and K and V are read ONCE for a slot's block of
``B`` rows: ``rows_read`` is, summed over the live slots, the rows a layer's
attention reads (a slot's length and its block).  A prefill's attention is
counted under the mask that is causal by blocks: a query reads every
position up to the end of its own block, and the prefill needs no head
(the published sampler uses a prompt for K and V alone)."""

from benchmark.reference import sdar_engine as ref

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
        "norms_layer": 2 * e + 2 * hd, "ln_f": e}


def held_parameters(config):
    """Every parameter this chip holds."""
    z, p = ref.sizes(config), parameters(config)
    return (z["layers"] * (p["attention"] + p["norms_layer"] + p["router"]
                           + z["experts_held"] * p["expert"])
            + p["embed"] + p["head"] + p["ln_f"])


def row_bytes(config):
    """K and V of one row of one layer."""
    z = ref.sizes(config)
    return 2 * z["kv_heads"] * z["head_dim"] \
        * _BYTES[config["precision"]["kv_cache"]]


def cache_bytes(config, slots):
    """K and V of the whole cache: ``max_len`` rows a slot a layer."""
    z = ref.sizes(config)
    return slots * row_bytes(config) * z["layers"] * z["max_len"]


def attention_bytes(config, rows_read):
    """K and V of the rows the layers read in one pass."""
    return row_bytes(config) * ref.sizes(config)["layers"] * rows_read


def attention_flops(config, rows_read):
    """Scores and weighted sums of a pass: each of a block's ``B`` rows,
    every head, over the rows its slot reads."""
    z = ref.sizes(config)
    return 4 * z["heads"] * z["head_dim"] * z["block"] * z["layers"] \
        * rows_read


def _weight_bytes(config, experts_hit, head=True):
    """Every weight a pass over all layers reads once, ``experts_hit`` of
    the experts (summed over the layers); router matrices and gains are
    float32; of the embedding only the rows' own lines, left out."""
    z, p = ref.sizes(config), parameters(config)
    wb = _BYTES[config["precision"]["weights"]]
    return wb * (z["layers"] * p["attention"] + experts_hit * p["expert"]
                 + (p["head"] if head else 0)) \
        + 4 * (z["layers"] * (p["router"] + p["norms_layer"]) + p["ln_f"])


def step_bytes(config, experts_hit, rows_read):
    """Bytes one pass has to read.  ``experts_hit``: experts that took
    part, summed over the layers; ``rows_read``: the rows a layer's
    attention reads for the live slots."""
    return _weight_bytes(config, experts_hit) \
        + attention_bytes(config, rows_read)


def step_flops(config, rows, local_picks, rows_read):
    """Operations of one pass over ``rows`` rows (``B`` a live slot): two
    a parameter and row for what every row passes (attention's projections,
    the router, the head), two a parameter for each of the ``local_picks``
    (row, held expert) pairs, and the attention over the rows held."""
    z, p = ref.sizes(config), parameters(config)
    every_row = z["layers"] * (p["attention"] + p["router"]) + p["head"]
    return 2 * rows * every_row + 2 * local_picks * p["expert"] \
        + attention_flops(config, rows_read)


def _pairs(n, block):
    """(query, key) pairs under the mask that is causal by blocks over
    ``n`` positions: a query reads up to the end of its own block."""
    return sum(min((p // block + 1) * block, n) for p in range(n))


def flash_flops(config, bucket):
    """Operations of one call of the prompt's attention kernel over a
    bucket: scores and weighted sums, two operations a multiply-add."""
    z = ref.sizes(config)
    return 4 * z["heads"] * z["head_dim"] * _pairs(bucket, z["block"])


def flash_bytes(config, bucket):
    """Bytes of one call: Q and the context once, K and V once."""
    z = ref.sizes(config)
    wb = _BYTES[config["precision"]["weights"]]
    return wb * bucket * z["head_dim"] * 2 * (z["heads"] + z["kv_heads"])


def prefill_flops(config, bucket):
    """Operations of one prefill of a bucket: every layer over every
    position (projections, router, ``top_k`` experts a row when all are
    held, attention under the blocked mask) but the last layer's, of which
    only K and V are kept; no head."""
    z, p = ref.sizes(config), parameters(config)
    local = z["top_k"] * z["experts_held"] / z["num_experts"]
    kv = 2 * z["embed"] * z["kv_heads"] * z["head_dim"]
    return 2 * bucket * ((z["layers"] - 1) * (
        p["attention"] + p["router"] + local * p["expert"]) + kv) \
        + (z["layers"] - 1) * flash_flops(config, bucket)


def prefill_bytes(config, bucket):
    """Bytes of one prefill: every weight but the last layer's beyond its K
    and V once (a prompt's rows hit every expert), K and V of the bucket
    written."""
    z, p = ref.sizes(config), parameters(config)
    wb = _BYTES[config["precision"]["weights"]]
    kv = 2 * z["embed"] * z["kv_heads"] * z["head_dim"]
    return _weight_bytes(config, (z["layers"] - 1) * z["experts_held"],
                         head=False) \
        - wb * (p["attention"] - kv) \
        + row_bytes(config) * z["layers"] * bucket
