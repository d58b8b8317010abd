"""The benchmark's own count of the operations of one window through a
pre-norm decoder of the ``jamba`` family without expert layers — state-space
(Mamba-1) mixers with an attention layer every ``attn_layer_period`` layers,
a gated MLP after every mixer — as the mathematics needs them whatever
implements it: 2 × multiply-accumulates of every product (the state-space
mixer's four projections and its taps, attention's projections, its scores
and values over the causal pairs only, the MLPs, the head over the vocabulary
for the positions that have a next token), and the recurrence at 7 operations
a (position, channel, state): ``Δ·A``, ``exp``, ``Δx·B``, the update's
multiply and add, ``s·C``'s multiply and add. And, for the selective-scan
kernel's share of its roofline, the operations and the bytes of one layer's
call: the operands the mathematics must move, each once. Takes the
configuration's file; consults nothing of the program."""

RECURRENCE_OPS = 7      # a (position, channel, state), see above


def layer_kinds(config):
    """How many of the layers are state-space ones and how many attention."""
    layers = config["num_hidden_layers"]
    attention = sum(i % config["attn_layer_period"]
                    == config["attn_layer_offset"] for i in range(layers))
    if config["num_experts"] != 1:
        raise ValueError("the count knows the stack without expert layers")
    return layers - attention, attention


def inner(config):
    return config["mamba_expand"] * config["hidden_size"]


def macs_per_window(config, window):
    """Multiply-accumulates of one window of ``window`` tokens, by part."""
    hidden, heads = config["hidden_size"], config["num_attention_heads"]
    width, kv_heads = hidden // heads, config["num_key_value_heads"]
    ssm, attention = layer_kinds(config)
    channels, states = inner(config), config["mamba_d_state"]
    rank = config["mamba_dt_rank"]
    return {
        "ssm_projections": ssm * window * (
            hidden * 2 * channels + channels * (rank + 2 * states)
            + rank * channels + channels * hidden),
        "ssm_taps": ssm * window * channels * config["mamba_d_conv"],
        "attention_projections": attention * window * (
            2 * hidden * heads * width + 2 * hidden * kv_heads * width),
        "attention_scores_values": attention * heads
        * (window * (window + 1) // 2) * 2 * width,
        "mlp": (ssm + attention) * window * 3 * hidden
        * config["intermediate_size"],
        "head": (window - 1) * hidden * config["vocab_size"],
    }


def scan_kernel_ops(config, window):
    """Operations of one layer's recurrence over one window."""
    return RECURRENCE_OPS * window * inner(config) * config["mamba_d_state"]


def scan_kernel_bytes(config, window):
    """Bytes one layer's recurrence must move over one window: per position
    ``x``, ``z`` and ``y`` at 2 bytes and ``Δ`` at 4 a channel, ``B`` and
    ``C`` at 4 a state."""
    return window * (inner(config) * (2 + 2 + 2 + 4)
                     + 2 * config["mamba_d_state"] * 4)


def window_flops(config, window):
    return 2 * sum(macs_per_window(config, window).values()) \
        + layer_kinds(config)[0] * scan_kernel_ops(config, window)
