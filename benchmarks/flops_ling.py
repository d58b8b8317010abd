"""The benchmark's own count of the operations of one window through a
pre-norm decoder of the ``bailing_hybrid`` family — linear attention with
the gated delta rule (KDA) where ``(i + 1) % layer_group_size != 0``, latent
attention (MLA) in the others, a dense MLP in the leading layers and after
them sparse-expert layers with a shared expert: as the mathematics needs
them whatever implements it, 2 × multiply-accumulates of

- KDA's projections (q, k, v, the decay's W_f, β, the gate, W_o), its taps,
  and its recurrence in the chunked form at ``CHUNK`` positions: per chunk
  and head the two triangles of decayed key products (keys and queries
  against keys, C² d each), the triangle's inverse (C³ / 6), the updates
  and the chunk's own outputs (C² d each) and the three products with the
  state (C d² each);
- MLA's projections, gate and output, and its scores and values over the
  causal pairs;
- the dense MLP, the router, the routed experts at the share of a token's
  ``num_experts_per_tok`` pairs that meet an expert held here, the shared
  expert, and the head over the vocabulary for the positions that have a
  next token.

``delta_kernel_ops`` / ``delta_kernel_bytes`` are one call of the KDA
recurrence over one window (one layer): its chunked products as above, and
the bytes its mechanism needs through HBM once — q, k, v in bfloat16, the
decays in float32, β, and the output in bfloat16 — whatever chunk and
operands a kernel uses. Takes the configuration's file; consults nothing of
the program."""

CHUNK = 64


def layer_kinds(config):
    """Each held layer's mixer, ``"kda"`` or ``"mla"``, by published index."""
    period = config["layer_group_size"]
    return ["mla" if (i + 1) % period == 0 else "kda"
            for i in config["layers_held"]]


def recurrence_macs(config, window):
    """The KDA recurrence's chunked products over one window, all heads."""
    d, heads = config["head_dim"], config["num_attention_heads"]
    chunks = -(-window // CHUNK)
    per_chunk = 4 * CHUNK * CHUNK * d + CHUNK ** 3 // 6 + 3 * CHUNK * d * d
    return heads * chunks * per_chunk


def macs_per_window(config, window):
    """Multiply-accumulates of one window of ``window`` tokens, by part."""
    hidden, d = config["hidden_size"], config["head_dim"]
    heads = config["num_attention_heads"]
    wide = heads * d
    kinds = layer_kinds(config)
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError(f"{config['num_hidden_layers']} layers, {kinds}")
    kda, mla = kinds.count("kda"), kinds.count("mla")
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v, rank = config["v_head_dim"], config["kv_lora_rank"]
    sparse = len(kinds) - config["first_k_dense_replace"]
    experts = config["published"]["num_experts"]
    held_share = len(config["experts_held"]) / experts
    expert = 3 * hidden * config["moe_intermediate_size"]
    return {
        "kda_projections": kda * window * (
            5 * hidden * wide + 2 * hidden * heads),
        "kda_taps": kda * window * 3 * wide * config["short_conv_kernel_size"],
        "kda_recurrence": kda * recurrence_macs(config, window),
        "mla_projections": mla * window * (
            hidden * heads * (nope + rope) + hidden * (rank + rope)
            + rank * heads * (nope + v) + hidden * heads + heads * v * hidden),
        "mla_scores_values": mla * heads * window * (window + 1) // 2 * (
            nope + rope + v),
        "dense_mlp": window * config["first_k_dense_replace"] * 3 * hidden
        * config["intermediate_size"],
        "router": sparse * window * hidden * experts,
        "routed_experts": sparse * window * config["num_experts_per_tok"]
        * held_share * expert,
        "shared_expert": sparse * window * 3 * hidden
        * config["moe_shared_expert_intermediate_size"],
        "head": (window - 1) * hidden * config["vocab_size"],
    }


def window_flops(config, window):
    return 2 * sum(macs_per_window(config, window).values())


def delta_kernel_ops(config, window):
    return 2 * recurrence_macs(config, window)


def delta_kernel_bytes(config, window):
    wide = config["num_attention_heads"] * config["head_dim"]
    return window * (wide * (2 + 2 + 2 + 4 + 2)
                     + config["num_attention_heads"] * 4)
