"""Latent-attention sparse-expert decoder as a prefill-only window scorer
(the ``pangu_ultra_moe`` family), run by ``DeepSequenceScorer`` from
``registry.SEQUENCE_MODELS``.

One block is: multi-head latent attention (queries and keys/values through
low-rank latents, a rotary part shared by all heads of the key;
``models/attention.py`` :func:`latent_attention`), sandwich norms (an
RMSNorm before AND after each sub-layer), and either a gated MLP or the
sparse expert layer (``models/experts.py``: a sigmoid router over every
published expert, the top-k renormalised and scaled) with one shared expert
beside the routed ones.

Precision follows the weights: matrix products run in the weights' dtype
(bfloat16 as the executor ships them) with float32 accumulation; the router,
the norms' statistics, the soft-maxes, the residual stream and the
log-probabilities are float32.

Outputs per window (row): ``pooled`` and ``logprobs``
(``models/scoring_head.py``); ``expert_counts`` — per expert layer and
published expert, the tokens of the window routed to it; and, under
``telemetry.PROGRAM_COUNTS``, the counters the executor records.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.core import telemetry
from sparkdl_tpu.models.attention import Rope, latent_attention
from sparkdl_tpu.models.experts import (ExpertConfig, expert_stats,
                                        routed_experts)
from sparkdl_tpu.models.scoring_head import program_counts, score_head
from sparkdl_tpu.models.sequence_ops import gated_mlp, rms_norm


@dataclass(frozen=True)
class LatentMoEConfig:
    """Widths as published; ``layers``/``dense_layers``, the expert layer's
    ``experts_held`` and ``vocab`` are what this chip holds of a stated
    deployment."""

    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int                 # per-head width without position
    rope: int                 # per-head rotary width (one key head for all)
    v: int
    dense_width: int
    vocab: int                # rows of embedding and head held here
    layers: int
    dense_layers: int
    moe: Optional[ExpertConfig] = None      # None: no expert layer
    eps: float = 1e-5
    theta: float = 10000.0
    query_block: int = 512


@functools.partial(jax.jit, static_argnames="c")
def block(layer, h, c: LatentMoEConfig):
    """One sandwich block over windows h (B, T, hidden) float32. Returns
    ``(h, fused, stats)``: ``fused`` (B,) as :func:`causal_attention` returns
    it for each window; ``stats`` is None for a dense layer. Jitted, so that
    a stack's layers of one kind are traced and lowered once, not once each:
    a kernel's trace is the dearest part of a warm start."""
    B, T, _ = h.shape
    with jax.named_scope("latent_attention"):
        attended, fused = lax.map(
            lambda row: latent_attention(
                layer["attn"], rms_norm(row, layer["input_norm"], c.eps),
                c.heads, c.eps, Rope(c.theta), c.query_block),
            h)
    h = h + rms_norm(attended, layer["post_attn_norm"], c.eps)
    x = rms_norm(h, layer["pre_mlp_norm"], c.eps)
    if "moe" not in layer:
        m = lax.map(lambda row: gated_mlp(layer["mlp"], row), x)
        return h + rms_norm(m, layer["post_mlp_norm"], c.eps), fused, None
    moe = layer["moe"]
    flat = x.reshape(B * T, -1)
    with jax.named_scope("routed_experts"):
        routed, told = routed_experts(moe, flat, c.moe)
    m = (gated_mlp(moe["shared"], flat) + routed).reshape(B, T, -1)
    return (h + rms_norm(m, layer["post_mlp_norm"], c.eps), fused,
            expert_stats(told, B, c.moe))


def forward(params, tokens, c: LatentMoEConfig) -> Dict[str, Any]:
    """tokens (B, T) int32 ids of the slice → the outputs of the module's
    docstring."""
    h = params["embed"][tokens].astype(jnp.float32)
    stats = []
    fused_layers = jnp.zeros((tokens.shape[0],), jnp.int32)
    for layer in params["layers"]:
        h, fused, layer_stats = block(layer, h, c)
        fused_layers = fused_layers + fused
        if layer_stats is not None:
            stats.append(layer_stats)
    out, fused_head = score_head(params, h, tokens, c.eps)
    out.update(program_counts(stats, tokens, {
        telemetry.M_SEQUENCE_FUSED_ATTENTION_LAYERS: fused_layers,
        telemetry.M_SEQUENCE_FUSED_HEAD_WINDOWS: fused_head}))
    return out
