"""Parameters, slot-state bytes, and the operations and bytes a decode step,
a prefill and the chunked scan need, from the configuration's shapes and
the program's row counters (family ``granite_hybrid_engine``).

"Needs" is the algorithm's floor, not what a program happens to move: every
weight once; the recurrent state and the convolution's tail of every live
slot of every mamba layer read and written; K and V of the rows each
attention layer holds for the live slots."""

from benchmark.reference import granite_hybrid_engine as ref

_BYTES = {"float32": 4, "bfloat16": 2}


def kinds(config):
    """How many layers of each kind."""
    names = ref.sizes(config)["layer_types"]
    return {k: names.count(k) for k in ("mamba", "attention")}


def _widths(config):
    z = ref.sizes(config)
    d = z["m_heads"] * z["m_head_dim"]
    return z, d, d + 2 * z["d_state"]


def parameters(config):
    """Parameter counts: the matrices of one layer of each kind, the
    SwiGLU every layer has, the small float32 vectors of each, and the
    embedding (the head is the same matrix)."""
    z, d, conv = _widths(config)
    e = z["embed"]
    wide, kv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
    return {
        "mlp": 3 * e * z["ffn"],
        "mamba": e * (d + conv + z["m_heads"]) + d * e,
        "attention": e * (wide + 2 * kv) + wide * e,
        "embed": z["vocab"] * e,
        # float32: the two norms' gains a layer, a kind's own vectors
        "vectors_layer": 2 * e,
        "vectors_mamba": conv * (z["d_conv"] + 1) + 3 * z["m_heads"] + d,
        "vectors_final": e}


def held_parameters(config):
    """Every parameter the chip holds."""
    p, k = parameters(config), kinds(config)
    return (sum(k.values()) * (p["mlp"] + p["vectors_layer"])
            + k["mamba"] * (p["mamba"] + p["vectors_mamba"])
            + k["attention"] * p["attention"]
            + p["embed"] + p["vectors_final"])


def weight_bytes(config):
    """Bytes of the weights a step or a prefill reads: every matrix once
    in the weights' dtype (the embedding once, as the head), the vectors
    in float32."""
    p, k = parameters(config), kinds(config)
    layers = sum(k.values())
    matrices = layers * p["mlp"] + k["mamba"] * p["mamba"] \
        + k["attention"] * p["attention"] + p["embed"]
    vectors = layers * p["vectors_layer"] \
        + k["mamba"] * p["vectors_mamba"] + p["vectors_final"]
    return _BYTES[config["precision"]["weights"]] * matrices + 4 * vectors


def row_bytes(config):
    """K and V of one position of one attention layer."""
    z = ref.sizes(config)
    return 2 * z["kv_heads"] * z["head_dim"] \
        * _BYTES[config["precision"]["kv_cache"]]


def state_bytes(config):
    """One slot's recurrent state and convolution tail of one mamba
    layer, each in its own dtype."""
    z, d, conv = _widths(config)
    return d * z["d_state"] * _BYTES[config["precision"]["state"]] \
        + (z["d_conv"] - 1) * conv * _BYTES[config["precision"]["kv_cache"]]


def cache_bytes(config, slots):
    """The whole slot state by kind of entry."""
    z, k = ref.sizes(config), kinds(config)
    return {"full": slots * k["attention"] * z["max_len"]
            * row_bytes(config),
            "state": slots * k["mamba"] * state_bytes(config)}


def step_state_bytes(config, rows, rows_full):
    """The bytes of a step that are not weights, by mechanism: ``rows``
    live slots, ``rows_full`` the rows an attention layer holds for
    them."""
    k = kinds(config)
    return {"state": 2 * rows * k["mamba"] * state_bytes(config),
            "full": rows_full * k["attention"] * row_bytes(config)}


def step_bytes(config, rows, rows_full):
    """Bytes one decode step has to move."""
    return weight_bytes(config) + sum(step_state_bytes(
        config, rows, rows_full).values())


def _row_flops(config):
    """Operations one row takes through the matrices of every layer."""
    p, k = parameters(config), kinds(config)
    return 2 * (sum(k.values()) * p["mlp"] + k["mamba"] * p["mamba"]
                + k["attention"] * p["attention"])


def attention_flops(config, rows_full):
    """Scores and weighted sums of one query a head over ``rows_full``
    rows, all attention layers."""
    z = ref.sizes(config)
    return 4 * z["heads"] * z["head_dim"] * kinds(config)["attention"] \
        * rows_full


def step_flops(config, rows, rows_full):
    """Operations of one decode step over ``rows`` rows: two a parameter
    and row (the head is the embedding once more), the recurrence (six a
    state element: the decay's product, the push's two and its sum, the
    product and sum into ``y``), and attention over the rows held."""
    z, d, _ = _widths(config)
    return (rows * (_row_flops(config) + 2 * z["vocab"] * z["embed"])
            + 6 * rows * kinds(config)["mamba"] * d * z["d_state"]
            + attention_flops(config, rows_full))


def scan_flops(config, positions):
    """Operations of the chunked matrix form of ONE mamba layer's
    recurrence over ``positions`` (the kernel ``ssd_scan``): a chunk of
    ``Q`` positions is ``C B^T`` (``2 Q Q N``), the masked decay's product
    with ``dt x`` (``2 Q Q d_inner``, the whole square: the form's count,
    which a mask halves for no program), and the carried state's two
    (``S_prev C_t`` and ``x B^T``, ``2 Q N d_inner`` each)."""
    z, d, _ = _widths(config)
    q = min(positions, z["chunk"])
    n = z["d_state"]
    return positions // q * (2 * q * q * n + 2 * q * q * d + 4 * q * n * d)


def scan_bytes(config, positions):
    """Bytes that recurrence has to move, float32: ``x`` read and ``y``
    written a position, ``dt`` a head, ``B`` and ``C``; the state in and
    out once."""
    z, d, _ = _widths(config)
    return 4 * (positions * (2 * d + z["m_heads"] + 2 * z["d_state"])
                + 2 * z["d_state"] * d)


def prefill_flops(config, bucket):
    """Operations of one prefill of ``bucket`` positions: every layer over
    every position (attention over the causal half), the scans, the head
    for one row."""
    z = ref.sizes(config)
    k = kinds(config)
    return (bucket * _row_flops(config) + 2 * z["vocab"] * z["embed"]
            + k["mamba"] * scan_flops(config, bucket)
            + k["attention"] * 4 * z["heads"] * z["head_dim"]
            * bucket * bucket // 2)


def prefill_bytes(config, bucket):
    """Bytes of one prefill: every weight once, and one slot's state
    written (the rows of the bucket, the states and tails)."""
    k = kinds(config)
    return (weight_bytes(config)
            + k["attention"] * bucket * row_bytes(config)
            + k["mamba"] * state_bytes(config))
