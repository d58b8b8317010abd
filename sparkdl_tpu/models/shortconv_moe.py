"""The pre-norm decoder as a prefill-only window scorer — the stack of the
``lfm2_moe`` family (gated short convolutions among grouped-query attention
layers), of the ``mellum`` family (grouped-query attention in every layer,
sliding-window layers among full ones, each kind with its own rotary) and of
the ``jamba`` family (state-space mixers with an attention layer among them,
no rotary, no expert layer), run by ``DeepSequenceScorer`` exactly as the
latent-attention model of ``registry.SEQUENCE_MODELS`` is.

``h ← h + mixer(RMSNorm(h))``, ``h ← h + ffn(RMSNorm(h))``. **Three mixers**,
by position, and a layer's weights say which it has. A layer that holds
``"conv"`` runs the **gated short convolution**: one projection to three
parts ``[B ; C ; x̃]``, a depthwise causal convolution of a few taps over
``B ⊙ x̃`` (zeros before the window: nothing outlives a launch), the gate
``C``, an output projection. A layer that holds ``"ssm"`` runs the
**state-space mixer** (``models/state_space.py``: the same taps with a bias,
inner norms, and a recurrence along the window whose state starts from zero
in each). A layer that holds ``"attn"`` runs **grouped-query attention**:
fewer key heads than query heads, an RMSNorm per head on queries and keys
where the weights hold one (``"q_norm"``), rotary on both where the config
gives a ``theta``; query head ``a`` reads key head ``a // (heads /
kv_heads)`` and keys and values are never repeated per query head. What the
weights cannot say — a number is no array — comes from the
config by position, the held layers being the leading ones: where it names
its layers' kinds (``layer_types``), a ``"sliding_attention"`` layer's query
reads the last ``span`` keys only, its own among them, and a kind's rotary
is the one ``rope`` gives it (``latent_moe.Rope``: plain, or YaRN's scaled
frequencies and amplitude). The ffn is a gated MLP where the layer holds
``"mlp"`` — every layer may: **the expert layer is optional** — else the
sparse expert layer, the one the latent-attention model runs
(``latent_moe.routed_experts``), here without a shared expert, its router's
scores sigmoids with a selection bias or a soft-max (``scoring``), and
usually held whole.

Shared with ``models/latent_moe.py`` and imported from it: ``rms_norm``, the
mixed-precision product, ``rotary``, the causal taps, causal attention over
grouped keys with its two paths (``grouped_causal_attention``),
``gated_mlp``, ``route`` / ``routed_experts`` / ``expert_stats``, and the
scorer's head and counts (``score_head``, ``program_counts``). Precision
follows the weights, as there: bfloat16 products with float32 accumulation;
the router, the norms' statistics, the soft-max, the rotary tables, the gates
and taps of the convolution, the state-space mixer's recurrence, the
residual stream and the log-probabilities float32.

Attention goes down ``latent_moe``'s fused kernel where a lowering for a TPU
finds bfloat16 weights, a window of whole query tiles and a head width the
kernel takes — 128, whole lanes: every layer of the ``mellum`` family, a
sliding layer visiting only the key tiles its span reaches, and the ``jamba``
family's, 20 query heads reading one key head; 64, half a lane group: every
attention layer of the ``lfm2_moe`` family, a key head's four query heads
worked together a grid step — and down the blocked path everywhere else,
with the same span. Every stack, with an expert layer or without, reports the
tokens it scored, the layers that took the attention kernel
(``sparkdl.sequence.fused_attention_layers``) and whether the head — tied or
not, read as it lies — went down its fused scoring head
(``sparkdl.sequence.fused_head_windows``). The expert layers' grouped
products go down ``latent_moe``'s grouped-product kernel at the published
widths (``sparkdl.moe.fused_product_layers``), and a state-space layer's
recurrence down the selective-scan kernel
(``sparkdl.sequence.fused_scan_layers``).
Outputs per window are ``latent_moe``'s, ``expert_counts`` only from a stack
with expert layers; the program's counts gain
``sparkdl.sequence.conv_layers``, from a stack that names its layers' kinds
``sparkdl.sequence.window_attention_layers`` and
``sparkdl.sequence.scored_keys``, and from one with state-space layers
``sparkdl.sequence.ssm_layers`` and ``sparkdl.sequence.fused_scan_layers``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.core import telemetry
from sparkdl_tpu.models.latent_moe import (
    Rope, _dot, causal_taps, expert_stats, gated_mlp,
    grouped_causal_attention, program_counts, rms_norm, rotary,
    routed_experts, score_head)
from sparkdl_tpu.models.state_space import state_space


@dataclass(frozen=True)
class ShortConvMoEConfig:
    """Widths as published; ``experts_held`` and ``vocab`` are what this chip
    holds of a stated deployment. How many layers there are, and which mixer
    and ffn each has, is read off the weights; the kind of an attention
    layer, where a model has several, by position off ``layer_types``. A
    model without expert layers leaves the expert fields at none, one
    without state-space layers the state-space sizes, and one whose
    attention turns nothing gives no ``theta``."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    dense_width: int
    expert_width: int = 0
    experts: int = 0          # published: the router's width
    experts_held: Tuple[int, ...] = ()
    top_k: int = 0
    vocab: int = 0            # rows of the embedding (and tied head) held
    scaling: float = 1.0
    norm_topk: bool = True
    topk_eps: float = 1e-6    # added to the chosen scores' sum before dividing
    scoring: str = "sigmoid"  # the router's scores: "sigmoid" or "softmax"
    eps: float = 1e-5
    theta: Optional[float] = 1000000.0      # None: no rotary
    # the published kind of every layer by position ("sliding_attention",
    # "full_attention"), where attention layers differ by kind; the keys a
    # sliding layer's query reads; and the kinds whose rotary is not the
    # plain one of ``theta``, each with its own
    layer_types: Tuple[str, ...] = ()
    span: int = 0
    rope: Tuple[Tuple[str, Rope], ...] = ()
    # the state-space mixer's sizes (``models/state_space.py``): channels,
    # states a channel, the width Δ is projected through, the taps
    d_inner: int = 0
    d_state: int = 0
    dt_rank: int = 0
    d_conv: int = 0
    capacity_factor: float = 2.0    # see LatentMoEConfig
    query_block: int = 512


def short_conv(p, u):
    """The gated short convolution over windows u (B, T, hidden) float32:
    ``W_out (C ⊙ conv(B ⊙ x̃))`` with ``[B ; C ; x̃] = W_in u``. The taps
    ``p["taps"]`` (hidden, L) weigh positions t − (L − 1) … t of each channel;
    gates and taps are float32."""
    taps = p["taps"].astype(jnp.float32)
    gate_in, gate_out, carried = jnp.split(_dot(u, p["in"]), 3, -1)
    return _dot(gate_out * causal_taps(gate_in * carried, taps), p["out"])


def grouped_attention(p, u, c: ShortConvMoEConfig, kind=None):
    """u (T, hidden) float32, one window → ``((T, hidden) float32, fused,
    scored)``, the last two as ``latent_moe.grouped_causal_attention``
    returns them. Queries and keys are normed per head where the weights
    hold the gains (one of ``head_dim`` each) and, where ``c`` gives a
    ``theta``, rotated in float32 — by the rotary and, for
    ``"sliding_attention"``, within the span that ``c`` gives the layer's
    ``kind``; without one nothing tells positions apart but the causal mask —
    the queries carry the scores' scale, and the operands go to the attention in the weights' dtype, the keys and values
    once a key head."""
    T = u.shape[0]
    act = p["out"].dtype

    def heads(a):                           # (T, n · d) → (T, n, d)
        return a.reshape(T, -1, c.head_dim)

    def projected(name):
        a = heads(_dot(u, p[name]))
        gain = p.get(name + "_norm")
        return a if gain is None else rms_norm(a, gain, c.eps)

    rope = dict(c.rope).get(kind)       # None: the plain rotary of c.theta
    turn = {} if rope is None else {
        "frequencies": rope.frequencies(c.head_dim // 2),
        "amplitude": rope.amplitude}

    def placed(a, heads):           # (T, n, d) → (T, n · d), turned or not
        a = a.reshape(T, -1)
        return a if c.theta is None else rotary(a, c.theta, heads, **turn)

    q = projected("q") * c.head_dim ** -0.5
    k = projected("k")
    out, fused, scored = grouped_causal_attention(
        placed(q, c.heads), placed(k, c.kv_heads), _dot(u, p["v"], act),
        c.heads, c.query_block,
        c.span if kind == "sliding_attention" else None)
    return _dot(out, p["out"]), fused, scored


@functools.partial(jax.jit, static_argnames=("c", "kind"))
def block(layer, h, c: ShortConvMoEConfig, kind=None):
    """One pre-norm block over windows h (B, T, hidden) float32; ``kind`` the
    layer's published kind where ``c`` names its layers' (else the weights
    say which mixer it is). Returns ``(h, stats, told)``: ``stats`` is None
    for a dense layer; ``told`` what the mixer tells of each window, (B,)
    int32 — an attention layer ``"fused"`` and, of a named kind,
    ``"scored_keys"``, as ``grouped_attention`` returns them; a state-space
    layer ``"fused_scan"``, as ``state_space`` returns it — and empty for a
    convolution. Jitted, as ``latent_moe.block`` is: the stack's layers of
    one kind are traced and lowered once."""
    B, T, _ = h.shape
    u = rms_norm(h, layer["operator_norm"], c.eps)
    told = {}
    if "conv" in layer:
        with jax.named_scope("short_conv"):
            h = h + short_conv(layer["conv"], u)
    elif "ssm" in layer:
        with jax.named_scope("state_space"):
            mixed, _, fused = state_space(layer["ssm"], u, c)
        h = h + mixed
        told = {"fused_scan": fused}
    else:
        with jax.named_scope("sliding_attention" if kind == "sliding_attention"
                             else "grouped_attention"):
            attended, fused, scored = lax.map(
                lambda row: grouped_attention(layer["attn"], row, c, kind), u)
        h = h + attended
        told = {"fused": fused}
        if kind is not None:
            told["scored_keys"] = scored
    x = rms_norm(h, layer["ffn_norm"], c.eps)
    if "moe" not in layer:
        return (h + lax.map(lambda row: gated_mlp(layer["mlp"], row), x),
                None, told)
    with jax.named_scope("routed_experts"):
        routed, *stats = routed_experts(layer["moe"], x.reshape(B * T, -1), c)
    return h + routed.reshape(B, T, -1), expert_stats(*stats, B, c), told


def forward(params, tokens, c: ShortConvMoEConfig) -> Dict[str, Any]:
    """tokens (B, T) int32 ids → the outputs of ``latent_moe``'s docstring."""
    h = params["embed"][tokens].astype(jnp.float32)
    layers = params["layers"]
    kinds = c.layer_types[:len(layers)] or (None,) * len(layers)
    stats, told = [], []
    for layer, kind in zip(layers, kinds):
        h, layer_stats, layer_told = block(layer, h, c, kind)
        if layer_stats is not None:
            stats.append(layer_stats)
        told.append(layer_told)
    out, fused_head = score_head(params, h, tokens, c.eps)
    rows = tokens.shape[0]

    def summed(name):
        return sum((t[name] for t in told if name in t),
                   jnp.zeros((rows,), jnp.int32))

    def layers_with(mixer):
        return jnp.full((rows,), sum(mixer in layer for layer in layers),
                        jnp.int32)

    counts = {
        telemetry.M_SEQUENCE_FUSED_ATTENTION_LAYERS: summed("fused"),
        telemetry.M_SEQUENCE_FUSED_HEAD_WINDOWS: fused_head,
        telemetry.M_SEQUENCE_CONV_LAYERS: layers_with("conv")}
    if c.layer_types:
        counts[telemetry.M_SEQUENCE_WINDOW_ATTENTION_LAYERS] = jnp.full(
            (rows,), kinds.count("sliding_attention"), jnp.int32)
        counts[telemetry.M_SEQUENCE_SCORED_KEYS] = summed("scored_keys")
    if any("ssm" in layer for layer in layers):
        counts[telemetry.M_SEQUENCE_SSM_LAYERS] = layers_with("ssm")
        counts[telemetry.M_SEQUENCE_FUSED_SCAN_LAYERS] = summed("fused_scan")
    out.update(program_counts(stats, tokens, counts))
    return out
