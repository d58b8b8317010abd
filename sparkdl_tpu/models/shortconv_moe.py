"""The pre-norm decoder as a prefill-only window scorer — the stack of the
``lfm2_moe`` family (gated short convolutions among grouped-query attention
layers), of the ``mellum`` family (grouped-query attention in every layer,
sliding-window layers among full ones, each kind with its own rotary), of
the ``jamba`` family (state-space mixers with an attention layer among them,
no rotary, no expert layer), of the ``laguna`` family (full and sliding
layers with their own query heads and rotaries, a sigmoid gate on each
head's output, a shared expert beside the routed ones) and of the
``bailing_hybrid`` family (linear attention with the gated delta rule in
five layers of six, latent attention in the sixth, group-limited routing),
run by ``DeepSequenceScorer`` exactly as the latent-attention model of
``registry.SEQUENCE_MODELS`` is.

``h ← h + mixer(RMSNorm(h))``, ``h ← h + ffn(RMSNorm(h))``. **Five mixers**,
by position, and a layer's weights say which it has. A layer that holds
``"kda"`` runs **linear attention with the gated delta rule**
(``models/linear_attention.py``), one that holds ``"mla"`` **latent
attention** (``attention.latent_attention``: queries straight from the
input, a per-head gate, the rotary ``rope`` gives ``"latent_attention"``,
adjacent dimensions paired where it says so). A layer that holds
``"conv"`` runs the **gated short convolution**: one projection to three
parts ``[B ; C ; x̃]``, a depthwise causal convolution of a few taps over
``B ⊙ x̃`` (zeros before the window: nothing outlives a launch), the gate
``C``, an output projection. A layer that holds ``"ssm"`` runs the
**state-space mixer** (``models/state_space.py``: the same taps with a bias,
inner norms, and a recurrence along the window whose state starts from zero
in each). A layer that holds ``"attn"`` runs **grouped-query attention**
(:func:`grouped_attention` over ``models/attention.py``): fewer key heads
than query heads, as many of each as the layer's weights have, per-head
norms and a per-head sigmoid gate where the weights hold them, rotary where
the config gives a ``theta``. What the weights cannot say — a number is no
array — comes from the config by position, the held layers being the
leading ones: where it names its layers' kinds (``layer_types``), a
``"sliding_attention"`` layer's query reads the last ``span`` keys only, its
own among them, and a kind's rotary is the one ``rope`` gives it. The ffn is a gated MLP where the layer holds ``"mlp"`` —
every layer may: **the expert layer is optional** — else the sparse expert
layer (``models/experts.py``), with a shared expert beside the routed ones
where the layer holds ``"shared"``, the SwiGLUs of both clamped where the
config gives the layer's position a limit (``swiglu_limits``).

Precision follows the weights: bfloat16 products with float32 accumulation;
the router, the norms' statistics, the soft-max, the rotary tables, the gates
and taps of the convolution, attention's gates, the state-space mixer's
recurrence, the residual stream and the log-probabilities float32. Outputs
per window are the latent-attention model's (``models/latent_moe.py``),
``expert_counts`` only from a stack with expert layers, and the counts that
each kind of layer adds are :func:`forward`'s.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.core import telemetry
from sparkdl_tpu.models.attention import (Rope, grouped_causal_attention,
                                          latent_attention, rotary)
from sparkdl_tpu.models.experts import (ExpertConfig, expert_stats,
                                        routed_experts)
from sparkdl_tpu.models.linear_attention import kda
from sparkdl_tpu.models.scoring_head import program_counts, score_head
from sparkdl_tpu.models.sequence_ops import (causal_taps, gated_mlp,
                                             mixed_dot, rms_norm)
from sparkdl_tpu.models.state_space import state_space


@dataclass(frozen=True)
class ShortConvMoEConfig:
    """Widths as published; the expert layer's ``experts_held`` and
    ``vocab`` are what this chip holds of a stated deployment. How many
    layers there are, and which mixer and ffn each has, is read off the
    weights; the kind of an attention layer, where a model has several, by
    position off ``layer_types``; a layer's query and key heads off its
    weights too (``heads`` is the published ``num_attention_heads``). A
    model without expert layers leaves ``moe`` at none, one without
    state-space layers the state-space sizes, and one whose attention turns
    nothing gives no ``theta``."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    dense_width: int
    vocab: int = 0            # rows of the embedding (and tied head) held
    moe: Optional[ExpertConfig] = None      # None: no expert layer
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
    # linear attention's floor of a channel's decay a position
    # (``kda_lower_bound``, models/linear_attention.py)
    kda_lower_bound: float = -5.0
    # per held layer, the routed and the shared experts' ``swiglu_limit``
    # (0: none; layers past the tuple's end have none)
    swiglu_limits: Tuple[Tuple[float, float], ...] = ()
    query_block: int = 512


def short_conv(p, u):
    """The gated short convolution over windows u (B, T, hidden) float32:
    ``W_out (C ⊙ conv(B ⊙ x̃))`` with ``[B ; C ; x̃] = W_in u``. The taps
    ``p["taps"]`` (hidden, L) weigh positions t − (L − 1) … t of each channel;
    gates and taps are float32."""
    taps = p["taps"].astype(jnp.float32)
    gate_in, gate_out, carried = jnp.split(mixed_dot(u, p["in"]), 3, -1)
    return mixed_dot(gate_out * causal_taps(gate_in * carried, taps), p["out"])


def grouped_attention(p, u, c: ShortConvMoEConfig, kind=None):
    """u (T, hidden) float32, one window → ``((T, hidden) float32, fused,
    scored)``, the last two as ``grouped_causal_attention`` returns
    them. The query and key heads are read off the weights (their
    columns ÷ ``head_dim``), so a model's kinds of layer may differ in them.
    Queries and keys are normed per head where the weights hold the gains
    (one of ``head_dim`` each) and, where ``c`` gives a ``theta``, rotated in
    float32 — by the rotary that ``c`` gives the layer's ``kind``, over the
    share of a head it turns, and for ``"sliding_attention"`` within the
    span; without one nothing tells positions apart but the causal mask —
    the queries carry the scores' scale, and the operands go to the attention
    in the weights' dtype, the keys and values once a key head. Where the
    weights hold a ``"gate"`` (hidden × query heads), each head's output is
    scaled by ``σ(u W_g)`` of its own, in float32, before ``W_o``."""
    T = u.shape[0]
    act = p["out"].dtype
    query_heads = p["q"].shape[1] // c.head_dim

    def heads(a):                           # (T, n · d) → (T, n, d)
        return a.reshape(T, -1, c.head_dim)

    def projected(name):
        a = heads(mixed_dot(u, p[name]))
        gain = p.get(name + "_norm")
        return a if gain is None else rms_norm(a, gain, c.eps)

    rope = dict(c.rope).get(kind)       # None: the plain rotary of c.theta
    turned = c.head_dim if rope is None else rope.turned(c.head_dim)
    turn = {} if rope is None else {
        "frequencies": rope.frequencies(turned // 2),
        "amplitude": rope.amplitude}

    def placed(a):                  # (T, n, d) → (T, n · d), turned or not
        n = a.shape[1]
        if c.theta is None:
            return a.reshape(T, -1)
        if turned == c.head_dim:
            return rotary(a.reshape(T, -1), c.theta, n, **turn)
        # a head's leading dimensions turn, by halves of them; the rest pass
        part = rotary(a[..., :turned].reshape(T, -1), c.theta, n, **turn)
        return jnp.concatenate([part.reshape(T, n, turned),
                                a[..., turned:]], -1).reshape(T, -1)

    q = projected("q") * c.head_dim ** -0.5
    k = projected("k")
    out, fused, scored = grouped_causal_attention(
        placed(q), placed(k), mixed_dot(u, p["v"], act), query_heads,
        c.query_block, c.span if kind == "sliding_attention" else None)
    if "gate" in p:
        gate = jax.nn.sigmoid(mixed_dot(u, p["gate"]))       # (T, query heads)
        out = (out.reshape(T, query_heads, -1) * gate[..., None]).reshape(
            T, -1)
    return mixed_dot(out, p["out"]), fused, scored


@functools.partial(jax.jit, static_argnames=("c", "kind", "limits"))
def block(layer, h, c: ShortConvMoEConfig, kind=None, limits=(0.0, 0.0)):
    """One pre-norm block over windows h (B, T, hidden) float32; ``kind`` the
    layer's published kind where ``c`` names its layers' (else the weights
    say which mixer it is); ``limits`` its routed and shared experts'
    ``swiglu_limit``. Returns ``(h, stats, told)``: ``stats`` is None
    for a dense layer; ``told`` what the mixer tells of each window, (B,)
    int32 — an attention layer ``"fused"`` and, of a named kind,
    ``"scored_keys"``, as ``grouped_attention`` returns them; a latent one
    ``"fused"``; a state-space layer ``"fused_scan"``, as ``state_space``
    returns it; a linear-attention layer ``"fused_linear"``, as ``kda``
    does — and empty for a convolution. Jitted, as ``latent_moe.block`` is:
    the stack's layers of one kind are traced and lowered once."""
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
    elif "kda" in layer:
        with jax.named_scope("linear_attention"):
            mixed, fused = kda(layer["kda"], u, c.eps, c.kda_lower_bound)
        h = h + mixed
        told = {"fused_linear": jnp.broadcast_to(fused, (B,))}
    elif "mla" in layer:
        rotation = dict(c.rope).get("latent_attention", Rope(c.theta))
        with jax.named_scope("latent_attention"):
            attended, fused = lax.map(lambda row: latent_attention(
                layer["mla"], row, c.heads, c.eps, rotation, c.query_block),
                u)
        h = h + attended
        told = {"fused": fused}
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
    flat = x.reshape(B * T, -1)
    with jax.named_scope("routed_experts"):
        routed, moe_told = routed_experts(layer["moe"], flat, c.moe,
                                          limits[0])
    if "shared" in layer["moe"]:
        with jax.named_scope("shared_expert"):
            routed = routed + gated_mlp(layer["moe"]["shared"], flat,
                                        limits[1])
    return (h + routed.reshape(B, T, -1), expert_stats(moe_told, B, c.moe),
            told)


def forward(params, tokens, c: ShortConvMoEConfig) -> Dict[str, Any]:
    """tokens (B, T) int32 ids → the outputs of ``latent_moe``'s docstring."""
    h = params["embed"][tokens].astype(jnp.float32)
    layers = params["layers"]
    kinds = c.layer_types[:len(layers)] or (None,) * len(layers)
    stats, told = [], []
    for i, (layer, kind) in enumerate(zip(layers, kinds)):
        limits = c.swiglu_limits[i] if i < len(c.swiglu_limits) \
            else (0.0, 0.0)
        h, layer_stats, layer_told = block(layer, h, c, kind, limits)
        if layer_stats is not None:
            stats.append(layer_stats)
        told.append(layer_told)
    out, fused_head = score_head(params, h, tokens, c.eps)
    rows = tokens.shape[0]

    def summed(name):
        return sum((t[name] for t in told if name in t),
                   jnp.zeros((rows,), jnp.int32))

    def layers_with(mixer, part=None):
        """Each row: the layers that hold ``mixer`` (within ``part``)."""
        return jnp.full((rows,), sum(
            mixer in (layer if part is None else layer.get(part, ()))
            for layer in layers), jnp.int32)

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
    if any("gate" in layer.get("attn", ()) for layer in layers):
        counts[telemetry.M_SEQUENCE_GATED_ATTENTION_LAYERS] = layers_with(
            "gate", "attn")
    if any("shared" in layer.get("moe", ()) for layer in layers):
        counts[telemetry.M_MOE_SHARED_EXPERT_LAYERS] = layers_with(
            "shared", "moe")
    if any("kda" in layer for layer in layers):
        counts[telemetry.M_SEQUENCE_LINEAR_ATTENTION_LAYERS] = layers_with(
            "kda")
        counts[telemetry.M_SEQUENCE_FUSED_LINEAR_ATTENTION_LAYERS] = summed(
            "fused_linear")
    if c.moe is not None and c.moe.groups > 1:
        counts[telemetry.M_MOE_GROUP_LIMITED_LAYERS] = layers_with("moe")
    out.update(program_counts(stats, tokens, counts))
    return out
