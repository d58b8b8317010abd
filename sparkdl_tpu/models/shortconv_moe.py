"""Short-convolution sparse-expert decoder (the ``lfm2_moe`` family) as a
prefill-only window scorer — the second sequence model of
``registry.SEQUENCE_MODELS``, run by ``DeepSequenceScorer`` exactly as the
latent-attention one is.

A pre-norm stack: ``h ← h + mixer(RMSNorm(h))``, ``h ← h + ffn(RMSNorm(h))``.
The mixer differs by position — a layer's weights say which it is. A layer
that holds ``"conv"`` runs the **gated short convolution**: one projection to
three parts ``[B ; C ; x̃]``, a depthwise causal convolution of a few taps
over ``B ⊙ x̃`` (zeros before the window: nothing outlives a launch), the
gate ``C``, an output projection. A layer that holds ``"attn"`` runs
**grouped-query attention**: fewer key heads than query heads, an RMSNorm per
head on queries and keys, rotary on both; query head ``a`` reads key head
``a // (heads / kv_heads)`` and keys and values are never repeated per query
head. The ffn is a gated MLP where the layer holds ``"mlp"``, else the sparse
expert layer — the one the latent-attention model runs
(``latent_moe.routed_experts``), here without a shared expert, with a
selection bias in the router, and usually held whole.

Shared with ``models/latent_moe.py`` and imported from it: ``rms_norm``, the
mixed-precision product, ``rotary``, the blocked causal soft-max, ``gated_mlp``,
``route`` / ``routed_experts`` / ``expert_stats``, and the scorer's head and
counts (``score_head``, ``expert_outputs``). Precision follows the weights, as
there: bfloat16 products with float32 accumulation; the router, the norms'
statistics, the soft-max, the gates and taps of the convolution, the residual
stream and the log-probabilities float32.

At a head width of 64 — half a lane group — the fused attention kernel of
``latent_moe`` does not apply (whole lanes, one rotary key head): attention
goes down the blocked path, and ``sparkdl.sequence.fused_attention_layers``
reads 0. The expert layers' grouped products do go down ``latent_moe``'s
grouped-product kernel at the published widths
(``sparkdl.moe.fused_product_layers``). Outputs per window are
``latent_moe``'s; the program's counts gain ``sparkdl.sequence.conv_layers``.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.core import telemetry
from sparkdl_tpu.models.latent_moe import (
    _blocked_attention, _dot, expert_outputs, expert_stats, gated_mlp,
    rms_norm, rotary, routed_experts, score_head)


@dataclass(frozen=True)
class ShortConvMoEConfig:
    """Widths as published; ``experts_held`` and ``vocab`` are what this chip
    holds of a stated deployment. How many layers there are, and which mixer
    and ffn each has, is read off the weights."""

    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    dense_width: int
    expert_width: int
    experts: int              # published: the router's width
    experts_held: Tuple[int, ...]
    top_k: int
    vocab: int                # rows of the embedding (and tied head) held
    scaling: float = 1.0
    norm_topk: bool = True
    topk_eps: float = 1e-6    # added to the chosen scores' sum before dividing
    eps: float = 1e-5
    theta: float = 1000000.0
    capacity_factor: float = 2.0    # see LatentMoEConfig
    query_block: int = 512


def short_conv(p, u):
    """The gated short convolution over windows u (B, T, hidden) float32:
    ``W_out (C ⊙ conv(B ⊙ x̃))`` with ``[B ; C ; x̃] = W_in u``. The taps
    ``p["taps"]`` (hidden, L) weigh positions t − (L − 1) … t of each channel;
    gates and taps are float32."""
    T = u.shape[1]
    taps = p["taps"].astype(jnp.float32)
    L = taps.shape[1]
    gate_in, gate_out, carried = jnp.split(_dot(u, p["in"]), 3, -1)
    z = jnp.pad(gate_in * carried, ((0, 0), (L - 1, 0), (0, 0)))
    c = sum(taps[:, j] * z[:, j:j + T] for j in range(L))
    return _dot(gate_out * c, p["out"])


def grouped_attention(p, u, c: ShortConvMoEConfig):
    """u (T, hidden) float32, one window → (T, hidden) float32. Queries and
    keys are normed per head (one gain of ``head_dim`` each) and rotated in
    float32, the queries carry the scores' scale, and the operands go to the
    blocked soft-max in the weights' dtype, the keys and values once a key
    head."""
    T = u.shape[0]
    act = p["out"].dtype

    def heads(a):                           # (T, n · d) → (T, n, d)
        return a.reshape(T, -1, c.head_dim)

    def heads_first(a):
        return jnp.swapaxes(heads(a), 0, 1).astype(act)

    q = rms_norm(heads(_dot(u, p["q"])), p["q_norm"], c.eps) \
        * c.head_dim ** -0.5
    k = rms_norm(heads(_dot(u, p["k"])), p["k_norm"], c.eps)
    out = _blocked_attention(
        heads_first(rotary(q.reshape(T, -1), c.theta, c.heads)),
        heads_first(rotary(k.reshape(T, -1), c.theta, c.kv_heads)),
        heads_first(_dot(u, p["v"], act)), c.query_block)
    return _dot(jnp.swapaxes(out, 0, 1).reshape(T, -1), p["out"])


@functools.partial(jax.jit, static_argnames="c")
def block(layer, h, c: ShortConvMoEConfig):
    """One pre-norm block over windows h (B, T, hidden) float32. Returns
    ``(h, stats)``; ``stats`` is None for a dense layer. Jitted, as
    ``latent_moe.block`` is: the stack's layers of one kind are traced and
    lowered once."""
    B, T, _ = h.shape
    u = rms_norm(h, layer["operator_norm"], c.eps)
    if "conv" in layer:
        with jax.named_scope("short_conv"):
            h = h + short_conv(layer["conv"], u)
    else:
        with jax.named_scope("grouped_attention"):
            h = h + lax.map(
                lambda row: grouped_attention(layer["attn"], row, c), u)
    x = rms_norm(h, layer["ffn_norm"], c.eps)
    if "moe" not in layer:
        return h + lax.map(lambda row: gated_mlp(layer["mlp"], row), x), None
    with jax.named_scope("routed_experts"):
        routed, *told = routed_experts(layer["moe"], x.reshape(B * T, -1), c)
    return h + routed.reshape(B, T, -1), expert_stats(*told, B, c)


def forward(params, tokens, c: ShortConvMoEConfig) -> Dict[str, Any]:
    """tokens (B, T) int32 ids → the outputs of ``latent_moe``'s docstring."""
    h = params["embed"][tokens].astype(jnp.float32)
    stats = []
    for layer in params["layers"]:
        h, layer_stats = block(layer, h, c)
        if layer_stats is not None:
            stats.append(layer_stats)
    out = score_head(params, h, tokens, c.eps)
    if stats:
        rows = tokens.shape[0]
        out.update(expert_outputs(stats, tokens, {
            telemetry.M_SEQUENCE_FUSED_ATTENTION_LAYERS: jnp.zeros(
                (rows,), jnp.int32),
            telemetry.M_SEQUENCE_CONV_LAYERS: jnp.full(
                (rows,), sum("conv" in layer for layer in params["layers"]),
                jnp.int32)}))
    return out
