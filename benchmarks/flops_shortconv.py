"""The benchmark's own count of a short-convolution sparse-expert decoder's
operations over one window (the ``lfm2_moe`` family), as the mathematics
needs them whatever implements it: 2 × multiply-accumulates of every
projection, the convolution's taps, attention's scores and values over the
causal pairs only, the dense MLP, the router, the routed experts at the share
of a token's ``num_experts_per_tok`` pairs that meet an expert held here
(exactly that many where all are held), the head over the vocabulary for the
positions that have a next token. (The plain reference's jaxpr cannot give
it: it computes every held expert for every token and masks.) Takes the
configuration's file; consults nothing of the program."""


def macs_per_window(config, window):
    """Multiply-accumulates of one window of ``window`` tokens, by part."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    width = hidden // heads
    kv_heads = config["num_key_value_heads"]
    kinds = config["layer_types"]
    conv, attention = kinds.count("conv"), kinds.count("full_attention")
    layers, dense = config["num_hidden_layers"], config["num_dense_layers"]
    if conv + attention != layers:
        raise ValueError(f"{layers} layers, {kinds}")
    expert = 3 * hidden * config["moe_intermediate_size"]
    held_share = len(config["experts_held"]) / config["num_experts"]
    causal_pairs = window * (window + 1) // 2
    return {
        "conv_projections": conv * window * (hidden * 3 * hidden
                                             + hidden * hidden),
        "conv_taps": conv * window * hidden * config["conv_L_cache"],
        "attention_projections": attention * window * (
            2 * hidden * heads * width + 2 * hidden * kv_heads * width),
        "attention_scores_values": attention * heads * causal_pairs
        * 2 * width,
        "dense_mlp": dense * window * 3 * hidden
        * config["intermediate_size"],
        "router": (layers - dense) * window * hidden * config["num_experts"],
        "routed_experts": (layers - dense) * window
        * config["num_experts_per_tok"] * held_share * expert,
        "head": (window - 1) * hidden * config["vocab_size"],
    }


def window_flops(config, window):
    return 2 * sum(macs_per_window(config, window).values())
