"""The benchmark's own count of the operations of one window through a
pre-norm decoder whose attention layers are of two kinds — full, and sliding
with a span (the ``mellum`` family) — and whose every ffn is a sparse-expert
layer: as the mathematics needs them whatever implements it, 2 ×
multiply-accumulates of attention's projections, of its scores and values
over exactly the (query, key) pairs each kind of layer reads, of the router,
of the routed experts at the share of a token's ``num_experts_per_tok``
pairs that meet an expert held here (exactly that many where all are held),
and of the head over the vocabulary for the positions that have a next
token. (The plain reference's jaxpr cannot give it: it scores every pair and
masks, and computes every held expert for every token.) Takes the
configuration's file; consults nothing of the program."""


def attended_pairs(config, window, kind):
    """(query, key) pairs one head reads in one layer of ``kind``: key j for
    query t where j ≤ t and, in a sliding layer, j > t − sliding_window."""
    if kind == "full_attention":
        return window * (window + 1) // 2
    if kind != "sliding_attention":
        raise ValueError(f"no layer type {kind!r}")
    span = min(config["sliding_window"], window)
    return span * (span + 1) // 2 + (window - span) * span


def attention_kernel_flops(config, window, kind):
    """Operations of one layer's scores and values alone, over the pairs its
    kind reads: what a roofline share of the attention kernel divides by its
    device seconds (PERF.md §7 row 6)."""
    return 2 * config["num_attention_heads"] * attended_pairs(
        config, window, kind) * 2 * config["head_dim"]


def macs_per_window(config, window):
    """Multiply-accumulates of one window of ``window`` tokens, by part."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    width, kv_heads = config["head_dim"], config["num_key_value_heads"]
    kinds = config["layer_types"]
    layers = config["num_hidden_layers"]
    if len(kinds) != layers or set(config["mlp_layer_types"]) != {"sparse"}:
        raise ValueError(f"{layers} layers, {kinds}")
    expert = 3 * hidden * config["moe_intermediate_size"]
    held_share = len(config["experts_held"]) / config["num_experts"]
    return {
        "attention_projections": layers * window * (
            2 * hidden * heads * width + 2 * hidden * kv_heads * width),
        **{f"{kind}_scores_values": kinds.count(kind) * heads
           * attended_pairs(config, window, kind) * 2 * width
           for kind in ("full_attention", "sliding_attention")},
        "router": layers * window * hidden * config["num_experts"],
        "routed_experts": layers * window * config["num_experts_per_tok"]
        * held_share * expert,
        "head": (window - 1) * hidden * config["vocab_size"],
    }


def window_flops(config, window):
    return 2 * sum(macs_per_window(config, window).values())
