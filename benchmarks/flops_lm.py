"""The benchmark's own count of a latent-attention sparse-expert decoder's
operations over one window, as the mathematics needs them whatever
implements it: 2 × multiply-accumulates of every projection and MLP,
attention's scores and values over the causal pairs only, the routed experts
at the expected share of (token, expert) pairs that meet an expert held here,
the head over the slice for the positions that have a next token. (The plain
reference's jaxpr cannot give it: it computes every held expert for every
token and masks.) Takes the configuration's file; consults nothing of the
program."""


def macs_per_window(config, window):
    """Multiply-accumulates of one window of ``window`` tokens, by part."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    v = config["v_head_dim"]
    kv_rank, q_rank = config["kv_lora_rank"], config["q_lora_rank"]
    layers = config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    expert = 3 * hidden * config["moe_intermediate_size"]
    published = config["published"]["n_routed_experts"]
    held_share = len(config["experts_held"]) / published
    projections = (hidden * q_rank + q_rank * heads * qk
                   + hidden * (kv_rank + config["qk_rope_head_dim"])
                   + kv_rank * heads * (config["qk_nope_head_dim"] + v)
                   + heads * v * hidden)
    causal_pairs = window * (window + 1) // 2
    return {
        "attention_projections": layers * window * projections,
        "attention_scores_values": layers * heads * causal_pairs * (qk + v),
        "dense_mlp": dense * window * 3 * hidden
        * config["intermediate_size"],
        "router": (layers - dense) * window * hidden * published,
        "shared_experts": (layers - dense) * window
        * config["n_shared_experts"] * expert,
        "routed_experts": (layers - dense) * window
        * config["num_experts_per_tok"] * held_share * expert,
        "head": (window - 1) * hidden * config["vocab_size"],
    }


def window_flops(config, window):
    return 2 * sum(macs_per_window(config, window).values())
