"""Parameters, cache bytes, and the operations and bytes a decode step, the
step's latent attention and a prefill bucket need, from the configuration's
shapes and the program's counters (family ``deepseek_v2_engine``).

"Needs" is the algorithm's floor, not what a program happens to move: every
weight that takes part is read once — of the experts those that were hit,
not all that are held — and a latent row is the ``kv_lora_rank +
qk_rope_head_dim`` values the mathematics reads (1152 B in bfloat16), not
the 128-lane rows they lie in.  The step is counted in the absorbed form
(``heads x (2 kv_lora_rank + qk_rope_head_dim)`` multiply-adds a held row
and layer), a prefill in the expanded one."""

from benchmark.reference import deepseek_v2_engine as ref

_BYTES = {"float32": 4, "bfloat16": 2}
#: lanes a cached row is a whole number of on the chip
_LANES = 128


def parameters(config):
    """Parameter counts by part (one layer's where layers repeat)."""
    z = ref.sizes(config)
    e, h = z["embed"], z["heads"]
    return {
        "attention": e * z["q_rank"]
        + z["q_rank"] * h * (z["nope_dim"] + z["rope_dim"])
        + e * (z["kv_rank"] + z["rope_dim"])
        + z["kv_rank"] * h * (z["nope_dim"] + z["v_dim"])
        + h * z["v_dim"] * e,
        "dense": 3 * e * z["dense_ffn"],
        "shared": 3 * e * z["shared_ffn"],
        "expert": 3 * e * z["expert_ffn"],
        "router": e * z["num_experts"],
        "embed": z["vocab"] * e, "head": e * z["vocab"],
        "norms_layer": 2 * e + z["q_rank"] + z["kv_rank"], "ln_f": e}


def layers(config):
    """(dense layers, expert layers) among those held."""
    z = ref.sizes(config)
    dense = min(z["first_dense"], z["layers"])
    return dense, z["layers"] - dense


def held_parameters(config):
    """Every parameter this chip holds."""
    z, p = ref.sizes(config), parameters(config)
    dense, sparse = layers(config)
    return (z["layers"] * (p["attention"] + p["norms_layer"])
            + dense * p["dense"]
            + sparse * (p["shared"] + p["router"]
                        + z["experts_held"] * p["expert"])
            + p["embed"] + p["head"] + p["ln_f"])


def row_bytes(config):
    """What the mathematics reads of one token in one layer: the latent
    values and the rotated key."""
    z = ref.sizes(config)
    return (z["kv_rank"] + z["rope_dim"]) \
        * _BYTES[config["precision"]["kv_cache"]]


def cache_bytes(config, slots):
    """The latent cache as it lies in memory: ``max_len`` rows a slot and
    layer, the rotated key padded to whole lanes."""
    z = ref.sizes(config)
    lies = z["kv_rank"] + z["rope_dim"] + -z["rope_dim"] % _LANES
    return slots * z["layers"] * z["max_len"] * lies \
        * _BYTES[config["precision"]["kv_cache"]]


def latent_bytes(config, rows_latent):
    """The latent rows the layers read in one step (``rows_latent`` counts
    them over all layers)."""
    return row_bytes(config) * rows_latent


def latent_flops(config, rows_latent):
    """Operations of the absorbed attention over those rows: a row's
    scores over ``kv_rank + rope_dim`` and its share of the weighted sum
    over ``kv_rank``, for every head."""
    z = ref.sizes(config)
    return 2 * z["heads"] * (2 * z["kv_rank"] + z["rope_dim"]) * rows_latent


def _weight_bytes(config, experts_hit):
    """Every weight a pass over all layers reads once, ``experts_hit`` of
    the experts (summed over the layers); router matrices and gains are
    float32; of the embedding only the rows' own lines, left out."""
    z, p = ref.sizes(config), parameters(config)
    dense, sparse = layers(config)
    wb = _BYTES[config["precision"]["weights"]]
    return wb * (z["layers"] * p["attention"] + dense * p["dense"]
                 + sparse * p["shared"] + experts_hit * p["expert"]
                 + p["head"]) \
        + 4 * (sparse * p["router"] + z["layers"] * p["norms_layer"]
               + p["ln_f"])


def attention_weight_bytes(config):
    """Attention's own projections over all layers."""
    z, p = ref.sizes(config), parameters(config)
    return _BYTES[config["precision"]["weights"]] * z["layers"] \
        * p["attention"]


def step_bytes(config, experts_hit, rows_latent):
    """Bytes one decode step has to read.  ``experts_hit``: experts that
    took part, summed over the layers; ``rows_latent``: the latent rows
    the live slots hold, summed over the layers."""
    return _weight_bytes(config, experts_hit) \
        + latent_bytes(config, rows_latent)


def step_flops(config, rows, local_picks, rows_latent):
    """Operations of one decode step over ``rows`` rows: two a parameter
    and row for what every row passes (attention's projections with both
    halves of ``kv_b_proj``, the dense MLP, the shared experts, the
    router, the head), two a parameter for each of the ``local_picks``
    (row, held expert) pairs, and the absorbed attention over the rows
    held."""
    z, p = ref.sizes(config), parameters(config)
    dense, sparse = layers(config)
    every_row = z["layers"] * p["attention"] + dense * p["dense"] \
        + sparse * (p["shared"] + p["router"]) + p["head"]
    return 2 * rows * every_row + 2 * local_picks * p["expert"] \
        + latent_flops(config, rows_latent)


def flash_flops(config, bucket):
    """Operations of one call of the prompt's attention kernel over a
    bucket, expanded heads: scores over ``nope + rope`` and weighted sums
    over ``v``, two operations a multiply-add, causal pairs."""
    z = ref.sizes(config)
    return 2 * z["heads"] * (z["nope_dim"] + z["rope_dim"] + z["v_dim"]) \
        * (bucket * (bucket + 1) // 2)


def flash_bytes(config, bucket):
    """Bytes of that call: every head's queries and keys of ``nope + rope``
    values and its values and context of ``v`` a position, once each."""
    z = ref.sizes(config)
    return _BYTES[config["precision"]["weights"]] * z["heads"] * bucket \
        * 2 * (z["nope_dim"] + z["rope_dim"] + z["v_dim"])


def prefill_flops(config, bucket):
    """Operations of one prefill of a bucket: every layer over every
    position (projections, the expansion of K and V from the latent rows,
    the dense MLP or shared experts and router, the picks a row gives this
    share, the expanded attention), the head for one row."""
    z, p = ref.sizes(config), parameters(config)
    dense, sparse = layers(config)
    local = z["top_k"] * z["experts_held"] / z["num_experts"]
    return 2 * bucket * (z["layers"] * p["attention"] + dense * p["dense"]
                         + sparse * (p["shared"] + p["router"]
                                     + local * p["expert"])) \
        + z["layers"] * flash_flops(config, bucket) + 2 * p["head"]


def prefill_bytes(config, bucket):
    """Bytes of one prefill: every weight once (a prompt's rows hit every
    held expert), the latent rows of the bucket written."""
    z = ref.sizes(config)
    _, sparse = layers(config)
    return _weight_bytes(config, sparse * z["experts_held"]) \
        + row_bytes(config) * z["layers"] * bucket
