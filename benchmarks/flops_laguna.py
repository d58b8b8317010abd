"""The benchmark's own count of the operations of one window through a
pre-norm decoder of the ``laguna`` family — attention layers full or sliding
with a span, each kind with its own query heads and a sigmoid gate on each
head's output, a dense MLP in the leading layers and after them
sparse-expert layers with a shared expert: as the mathematics needs them
whatever implements it, 2 × multiply-accumulates of attention's projections
and gate, of its scores and values over exactly the (query, key) pairs each
kind of layer reads (``flops_window.attended_pairs``), of the dense MLP,
the router, the routed experts at the share of a token's
``num_experts_per_tok`` pairs that meet an expert held here, the shared
expert, and of the head over the vocabulary for the positions that have a
next token. (``flops_window`` refuses this layer list: it knows neither a
dense layer, nor heads by layer, the gate or the shared expert.) Takes the
configuration's file; consults nothing of the program."""

from flops_window import attended_pairs


def macs_per_window(config, window):
    """Multiply-accumulates of one window of ``window`` tokens, by part."""
    hidden, width = config["hidden_size"], config["head_dim"]
    kv_heads = config["num_key_value_heads"]
    layers = list(zip(config["layer_types"],
                      config["num_attention_heads_per_layer"],
                      config["mlp_layer_types"]))
    if len(layers) != config["num_hidden_layers"]:
        raise ValueError(f"{config['num_hidden_layers']} layers, {layers}")
    sparse = sum(mlp == "sparse" for _, _, mlp in layers)
    expert = 3 * hidden * config["moe_intermediate_size"]
    held_share = len(config["experts_held"]) / config["num_experts"]
    return {
        "attention_projections_and_gate": window * sum(
            2 * hidden * heads * width + 2 * hidden * kv_heads * width
            + hidden * heads for _, heads, _ in layers),
        **{f"{kind}_scores_values": sum(
            heads * attended_pairs(config, window, kind) * 2 * width
            for kinds, heads, _ in layers if kinds == kind)
           for kind in ("full_attention", "sliding_attention")},
        "dense_mlp": window * sum(mlp == "dense" for _, _, mlp in layers)
        * 3 * hidden * config["intermediate_size"],
        "router": sparse * window * hidden * config["num_experts"],
        "routed_experts": sparse * window * config["num_experts_per_tok"]
        * held_share * expert,
        "shared_expert": sparse * window * 3 * hidden
        * config["shared_expert_intermediate_size"],
        "head": (window - 1) * hidden * config["vocab_size"],
    }


def window_flops(config, window):
    return 2 * sum(macs_per_window(config, window).values())
