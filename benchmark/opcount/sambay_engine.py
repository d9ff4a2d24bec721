"""Parameters, slot-state bytes, and the operations and bytes a decode step
and a prefill need, from the configuration's shapes and the program's row
counters (family ``sambay_engine``).

"Needs" is the algorithm's floor, not what a program happens to move: every
weight once; the recurrent state and the convolution's tail of every live
slot read and written; K and V of the rows a ring holds for live slots (at
most the window each); and K and V of the rows the shared full layer holds
for them TIMES ITS READERS, the full layer itself and every cross layer
after it, since each reads them with queries of its own."""

from benchmark.reference import sambay_engine as ref

_BYTES = {"float32": 4, "bfloat16": 2}


def kinds(config):
    """How many layers of each kind."""
    names = ref.layer_kinds(ref.sizes(config))
    return {k: names.count(k) for k in ("mamba", "window", "full", "gmu",
                                        "cross")}


def parameters(config):
    """Parameter counts: the matrices of one layer of each kind, the
    SwiGLU every layer has, the small float32 vectors of each, and the
    embedding (the head is the same matrix)."""
    z = ref.sizes(config)
    e, hd, d, n, r = z["embed"], z["head_dim"], z["d_inner"], \
        z["d_state"], z["dt_rank"]
    wide, kv = z["heads"] * hd, z["kv_heads"] * hd
    return {
        "mlp": 3 * e * z["ffn"],
        "mamba": 2 * e * d + d * (r + 2 * n) + r * d + d * e,
        "gmu": 2 * e * d,
        "attention": e * (wide + 2 * kv) + wide * e,
        "cross": e * wide + wide * e,
        "embed": z["vocab"] * e,
        # float32: gains and biases of two LayerNorms a layer, and a kind's
        # own vectors
        "vectors_layer": 4 * e,
        "vectors_mamba": d * z["d_conv"] + 3 * d + d * n,
        "vectors_attention": wide + 2 * kv + e + 4 * hd + 2 * hd,
        "vectors_cross": wide + e + 4 * hd + 2 * hd,
        "vectors_final": 2 * e}


def held_parameters(config):
    """Every parameter the chip holds."""
    p, k = parameters(config), kinds(config)
    layers = sum(k.values())
    return (layers * (p["mlp"] + p["vectors_layer"])
            + k["mamba"] * (p["mamba"] + p["vectors_mamba"])
            + k["gmu"] * p["gmu"]
            + (k["window"] + k["full"]) * (p["attention"]
                                           + p["vectors_attention"])
            + k["cross"] * (p["cross"] + p["vectors_cross"])
            + p["embed"] + p["vectors_final"])


def weight_bytes(config):
    """Bytes of the weights a step or a prefill reads: every matrix once
    in the weights' dtype (the embedding once, as the head), the vectors
    in float32."""
    p, k = parameters(config), kinds(config)
    layers = sum(k.values())
    matrices = (layers * p["mlp"] + k["mamba"] * p["mamba"]
                + k["gmu"] * p["gmu"]
                + (k["window"] + k["full"]) * p["attention"]
                + k["cross"] * p["cross"] + p["embed"])
    vectors = (layers * p["vectors_layer"] + k["mamba"] * p["vectors_mamba"]
               + (k["window"] + k["full"]) * p["vectors_attention"]
               + k["cross"] * p["vectors_cross"] + p["vectors_final"])
    return _BYTES[config["precision"]["weights"]] * matrices + 4 * vectors


def row_bytes(config):
    """K and V of one position of one attention layer."""
    z = ref.sizes(config)
    return 2 * z["kv_heads"] * z["head_dim"] \
        * _BYTES[config["precision"]["kv_cache"]]


def state_bytes(config):
    """One slot's recurrent state (float32) and convolution tail of one
    state-space layer."""
    z = ref.sizes(config)
    return z["d_inner"] * (4 * z["d_state"] + (z["d_conv"] - 1)
                           * _BYTES[config["precision"]["kv_cache"]])


def cache_bytes(config, slots):
    """The whole slot state by kind of entry."""
    z, k = ref.sizes(config), kinds(config)
    return {"full": slots * k["full"] * z["max_len"] * row_bytes(config),
            "ring": slots * k["window"] * z["window"] * row_bytes(config),
            "state": slots * k["mamba"] * state_bytes(config)}


def readers(config):
    """Layers that read the shared full layer's K and V at a step."""
    k = kinds(config)
    return k["full"] + k["cross"]


def step_state_bytes(config, rows, rows_full, rows_ring):
    """The bytes of a step that are not weights, by mechanism: ``rows``
    live slots, ``rows_full`` the rows the shared layer holds for them,
    ``rows_ring`` the same with each slot's count capped at the window."""
    k = kinds(config)
    return {"state": 2 * rows * k["mamba"] * state_bytes(config),
            "ring": rows_ring * k["window"] * row_bytes(config),
            "shared": rows_full * readers(config) * row_bytes(config)}


def step_bytes(config, rows, rows_full, rows_ring):
    """Bytes one decode step has to move."""
    return weight_bytes(config) + sum(step_state_bytes(
        config, rows, rows_full, rows_ring).values())


def _row_flops(config, layers):
    """Operations one row takes through the matrices of these layers."""
    p = parameters(config)
    per = {"mamba": p["mamba"], "gmu": p["gmu"], "window": p["attention"],
           "full": p["attention"], "cross": p["cross"]}
    return 2 * sum(per[kind] + p["mlp"] for kind in layers)


def step_flops(config, rows, rows_full, rows_ring):
    """Operations of one decode step over ``rows`` rows: two a parameter
    and row (the head is the embedding once more), the recurrence (six a
    state element), and scores and weighted sums over the rows held."""
    z = ref.sizes(config)
    names = ref.layer_kinds(z)
    k = kinds(config)
    attend = 4 * z["heads"] * z["head_dim"] * 2     # both halves, V twice as wide
    return (rows * (_row_flops(config, names) + 2 * z["vocab"] * z["embed"])
            + 6 * rows * k["mamba"] * z["d_inner"] * z["d_state"]
            + attend * (k["window"] * rows_ring
                        + readers(config) * rows_full))


def prefill_flops(config, bucket):
    """Operations of one prefill of ``bucket`` positions: the layers up to
    the full one over every position (attention over the causal half), the
    layers after it and the head for one row."""
    z = ref.sizes(config)
    names = ref.layer_kinds(z)
    cut = names.index("full") + 1
    k = kinds(config)
    attend = 4 * z["heads"] * z["head_dim"] * 2
    near = min(bucket, z["window"])
    return (bucket * _row_flops(config, names[:cut])
            + _row_flops(config, names[cut:]) + 2 * z["vocab"] * z["embed"]
            + 6 * bucket * k["mamba"] * z["d_inner"] * z["d_state"]
            + attend * (k["window"] * bucket * near // 2
                        + k["full"] * bucket * bucket // 2
                        + k["cross"] * bucket))


def prefill_bytes(config, bucket):
    """Bytes of one prefill: every weight once, and one slot's state
    written (the rows of the bucket, the rings, the states)."""
    z, k = ref.sizes(config), kinds(config)
    return (weight_bytes(config) + k["full"] * bucket * row_bytes(config)
            + k["window"] * z["window"] * row_bytes(config)
            + k["mamba"] * state_bytes(config))


def scan_flops(config, positions):
    """Operations of the recurrence of ONE state-space layer over
    ``positions`` (the kernel ``ssm_scan``): a state element a position
    takes a product with ``dt``, an exponential, the decay's product, the
    push's two products and sum, and the product and sum into ``y``."""
    z = ref.sizes(config)
    return 8 * positions * z["d_state"] * z["d_inner"]


def scan_bytes(config, positions):
    """Bytes that recurrence has to move, float32: ``dt`` and ``u`` read
    and ``y`` written a position, ``b`` and ``c``, and ``a`` and the state
    in and out once."""
    z = ref.sizes(config)
    return 4 * (3 * positions * z["d_inner"] + 2 * positions * z["d_state"]
                + 3 * z["d_state"] * z["d_inner"])
