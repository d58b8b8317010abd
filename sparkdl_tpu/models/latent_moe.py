"""Latent-attention sparse-expert decoder as a prefill-only window scorer —
the sequence models' counterpart of the image zoo (``DeepSequenceScorer``,
``registry.SEQUENCE_MODELS``).

One block is: multi-head latent attention (queries and keys/values through
low-rank latents, a rotary part shared by all heads of the key), sandwich
norms (an RMSNorm before AND after each sub-layer), and either a gated MLP
or a sparse-expert layer — sigmoid router over every published expert, the
top-k renormalised and scaled, one shared expert beside the routed ones.

**The expert layer is told which experts it holds** (``experts_held``, the
chip's share under expert parallelism): it routes over all experts, computes
its own experts' part for the (token, expert) pairs routed to them and leaves
out what absent experts would add. On one chip there is no exchange; nothing
here stands in for absent chips. Pairs are grouped per held expert into a
buffer of ``capacity`` rows each, and **no pair is dropped**: pairs beyond an
expert's buffer are computed in further rounds of the same grouped product,
and counted.

Precision follows the weights: matrix products run in the weights' dtype
(bfloat16 as the executor ships them) with float32 accumulation; the router,
the norms' statistics, the soft-maxes, the residual stream and the
log-probabilities are float32.

Outputs per window (row): ``pooled`` — the mean over positions of the
final-norm hidden state; ``logprobs`` — ``log p(x[t+1] | x[≤t])`` under the
soft-max over the vocabulary slice held (the last is 0); ``expert_counts`` —
per expert layer and published expert, the tokens of the window routed to it;
and, under ``telemetry.PROGRAM_COUNTS``, the counters the executor records.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.core import telemetry


@dataclass(frozen=True)
class LatentMoEConfig:
    """Widths as published; ``layers``/``dense_layers``, ``experts_held`` and
    ``vocab`` are what this chip holds of a stated deployment."""

    hidden: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int                 # per-head width without position
    rope: int                 # per-head rotary width (one key head for all)
    v: int
    dense_width: int
    expert_width: int
    experts: int              # published: the router's width
    experts_held: Tuple[int, ...]
    top_k: int
    vocab: int                # rows of embedding and head held here
    layers: int
    dense_layers: int
    scaling: float = 1.0
    norm_topk: bool = True
    eps: float = 1e-5
    theta: float = 10000.0
    # rows of the grouped products' buffer, as a multiple of the pairs that
    # meet a held expert when routing is even (tokens·top_k·held/experts);
    # pairs beyond it take further rounds
    capacity_factor: float = 2.0
    query_block: int = 512


# -- pieces --------------------------------------------------------------------


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def _dot(x, w, out=jnp.float32):
    """x · w in the weights' dtype, accumulated in float32."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32
                   ).astype(out)


def rotary(x, theta):
    """x (..., T, rope) float32: the two halves pair up."""
    half = x.shape[-1] // 2
    t = jnp.arange(x.shape[-2], dtype=jnp.float32)
    angle = t[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32)
                                   / half)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def causal_attention(q, k, v, block):
    """q, k (H, T, d), v (H, T, dv) → (H, T, dv). Blocked over queries, each
    block against its causal prefix of keys only: the scores of one block are
    the largest temporary (H · block · T float32), not H · T²."""
    H, T, d = q.shape
    block = min(block, T)
    scale = d ** -0.5
    out = []
    for lo in range(0, T, block):
        hi = min(lo + block, T)
        scores = jnp.einsum("hqd,hkd->hqk", q[:, lo:hi], k[:, :hi],
                            preferred_element_type=jnp.float32) * scale
        mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        scores = jnp.where(mask, scores, -jnp.inf)
        weights = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
        total = jnp.sum(weights, -1, keepdims=True)
        part = jnp.einsum("hqk,hkd->hqd", weights.astype(v.dtype), v[:, :hi],
                          preferred_element_type=jnp.float32)
        out.append((part / total).astype(v.dtype))
    return jnp.concatenate(out, 1)


def latent_attention(p, x, c: LatentMoEConfig):
    """x (T, hidden) float32, one window → (T, hidden) float32."""
    T = x.shape[0]
    act = p["q_up"].dtype
    cq = rms_norm(_dot(x, p["q_down"]), p["q_norm"], c.eps)
    q = _dot(cq, p["q_up"], act).reshape(T, c.heads, c.nope + c.rope)
    down = _dot(x, p["kv_down"])
    ckv = rms_norm(down[:, :c.kv_rank], p["kv_norm"], c.eps)
    k_rope = rotary(down[:, c.kv_rank:], c.theta).astype(act)
    kv = _dot(ckv, p["kv_up"], act).reshape(T, c.heads, c.nope + c.v)
    q = jnp.swapaxes(q, 0, 1)
    q = jnp.concatenate(
        [q[..., :c.nope],
         rotary(q[..., c.nope:].astype(jnp.float32), c.theta).astype(act)],
        -1)
    k = jnp.concatenate(
        [jnp.swapaxes(kv[..., :c.nope], 0, 1),
         jnp.broadcast_to(k_rope, (c.heads, T, c.rope))], -1)
    v = jnp.swapaxes(kv[..., c.nope:], 0, 1)
    out = causal_attention(q, k, v, c.query_block)
    return _dot(jnp.swapaxes(out, 0, 1).reshape(T, c.heads * c.v), p["out"])


def gated_mlp(p, x):
    act = p["down"].dtype
    hidden = jax.nn.silu(_dot(x, p["gate"])) * _dot(x, p["up"])
    return _dot(hidden.astype(act), p["down"])


def route(router, x, c: LatentMoEConfig):
    """Float32 at full precision: (chosen ids (N, k), weights (N, k))."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                    router.astype(jnp.float32),
                                    precision=lax.Precision.HIGHEST))
    top, chosen = lax.top_k(scores, c.top_k)
    if c.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    return chosen, top * c.scaling


def routed_experts(p, x, c: LatentMoEConfig):
    """The held experts' part of the layer for tokens x (N, hidden) float32.

    The (token, expert) pairs that meet a held expert are sorted by expert
    into one flat buffer of ``capacity`` rows; a round gathers the buffer's
    tokens, runs the three grouped products (``lax.ragged_dot``, group sizes
    = each expert's pairs in the buffer) and adds every pair's weighted
    result to its token by a one-hot product on the MXU (a row scatter-add
    of the same rows takes twice as long on the chip: PERF.md §6). Pairs
    beyond the buffer take further rounds: none is dropped.

    Returns ``(y (N, hidden) float32, chosen (N, k), counts (held,) pairs per
    held expert, overflow (N·k,) bool per pair: computed beyond the first
    round)``."""
    N = x.shape[0]
    held = len(c.experts_held)
    chosen, weights = route(p["router"], x, c)
    slot_of = jnp.full((c.experts,), held, jnp.int32).at[
        jnp.asarray(c.experts_held, jnp.int32)].set(
            jnp.arange(held, dtype=jnp.int32))
    slots = slot_of[chosen].reshape(-1)            # (N·k); `held` = absent
    pairs = slots.shape[0]
    order = jnp.argsort(slots, stable=True)        # held experts' pairs first,
    counts = jnp.sum(slots[:, None] == jnp.arange(held)[None, :], 0)
    ends = jnp.cumsum(counts)                      # expert by expert
    starts = ends - counts
    local = ends[-1]
    capacity = max(8, -(-int(c.capacity_factor * N * c.top_k * held
                             / c.experts) // 8) * 8)
    flat_weights = weights.reshape(-1)
    experts = p["experts"]
    act = experts["down"].dtype
    xa = x.astype(act)

    def one_round(r, y):
        lo = r * capacity
        position = lo + jnp.arange(capacity)
        valid = position < local
        pair = order[jnp.clip(position, 0, pairs - 1)]
        token = pair // c.top_k
        rows = xa[token]                                   # (capacity, hidden)
        sizes = (jnp.clip(ends - lo, 0, capacity)
                 - jnp.clip(starts - lo, 0, capacity)).astype(jnp.int32)
        hidden = jax.nn.silu(lax.ragged_dot(
            rows, experts["gate"], sizes,
            preferred_element_type=jnp.float32)) * lax.ragged_dot(
            rows, experts["up"], sizes, preferred_element_type=jnp.float32)
        out = lax.ragged_dot(hidden.astype(act), experts["down"], sizes,
                             preferred_element_type=jnp.float32)
        weighted = jnp.where(valid[:, None],
                             out * flat_weights[pair][:, None], 0.0)
        to_token = (jnp.arange(N)[:, None] == token[None, :]) & valid[None, :]
        return y + jnp.dot(to_token.astype(act), weighted.astype(act),
                           preferred_element_type=jnp.float32)

    rounds = -(-local // capacity)          # 1 unless the held experts are full
    y = lax.fori_loop(0, rounds, one_round, jnp.zeros(x.shape, jnp.float32))
    overflow = jnp.zeros((pairs,), bool).at[order].set(
        (slots[order] < held) & (jnp.arange(pairs) >= capacity))
    return y, chosen, counts, overflow


def block(layer, h, c: LatentMoEConfig):
    """One sandwich block over windows h (B, T, hidden) float32. Returns
    ``(h, stats)``; ``stats`` is None for a dense layer."""
    B, T, _ = h.shape
    attended = lax.map(
        lambda row: latent_attention(
            layer["attn"], rms_norm(row, layer["input_norm"], c.eps), c), h)
    h = h + rms_norm(attended, layer["post_attn_norm"], c.eps)
    x = rms_norm(h, layer["pre_mlp_norm"], c.eps)
    if "moe" not in layer:
        m = lax.map(lambda row: gated_mlp(layer["mlp"], row), x)
        return h + rms_norm(m, layer["post_mlp_norm"], c.eps), None
    moe = layer["moe"]
    flat = x.reshape(B * T, -1)
    routed, chosen, counts, overflow = routed_experts(moe, flat, c)
    m = (gated_mlp(moe["shared"], flat) + routed).reshape(B, T, -1)
    held = jnp.asarray(c.experts_held, jnp.int32)
    per_row = jnp.sum(chosen.reshape(B, -1, 1) == jnp.arange(c.experts), 1)
    stats = {
        "expert_counts": per_row.astype(jnp.int32),              # (B, experts)
        "local_pairs": jnp.sum(per_row[:, held], -1).astype(jnp.int32),
        "overflow_pairs": jnp.sum(overflow.reshape(B, -1), -1
                                  ).astype(jnp.int32),
        # of the launch: the fullest held expert's pairs over the mean
        "load_max_over_mean": jnp.max(counts) / jnp.maximum(
            jnp.mean(counts.astype(jnp.float32)), 1.0),
    }
    return h + rms_norm(m, layer["post_mlp_norm"], c.eps), stats


def forward(params, tokens, c: LatentMoEConfig) -> Dict[str, Any]:
    """tokens (B, T) int32 ids of the slice → the outputs of the module's
    docstring."""
    B, T = tokens.shape
    h = params["embed"][tokens].astype(jnp.float32)
    stats = []
    for layer in params["layers"]:
        h, layer_stats = block(layer, h, c)
        if layer_stats is not None:
            stats.append(layer_stats)
    x = rms_norm(h, params["final_norm"], c.eps)

    def row_logprobs(args):
        row, ids = args
        logits = jnp.dot(row.astype(params["head"].dtype), params["head"].T,
                         preferred_element_type=jnp.float32)
        logp = jax.nn.log_softmax(logits, -1)
        nxt = jnp.take_along_axis(logp[:-1], ids[1:, None], -1)[:, 0]
        return jnp.pad(nxt, (0, 1))

    # an id outside the slice held would be clamped by the lookups: its
    # window's outputs are not a number instead
    known = jnp.all((tokens >= 0) & (tokens < params["embed"].shape[0]), 1)
    out = {"pooled": jnp.where(known[:, None], jnp.mean(x, 1), jnp.nan),
           "logprobs": jnp.where(known[:, None],
                                 lax.map(row_logprobs, (x, tokens)), jnp.nan)}
    if stats:
        def stacked(name):
            return jnp.stack([s[name] for s in stats], 1)

        out["expert_counts"] = stacked("expert_counts")
        out[telemetry.PROGRAM_COUNTS] = {
            telemetry.M_SEQUENCE_TOKENS: jnp.full((B,), T, jnp.int32),
            telemetry.M_MOE_ROUTED_TOKENS: jnp.full((B,), T * len(stats),
                                                    jnp.int32),
            telemetry.M_MOE_LOCAL_PAIRS: jnp.sum(stacked("local_pairs"), 1),
            telemetry.M_MOE_OVERFLOW_PAIRS: jnp.sum(
                stacked("overflow_pairs"), 1),
            telemetry.M_MOE_LOAD_MAX_OVER_MEAN: jnp.broadcast_to(
                jnp.stack([s["load_max_over_mean"] for s in stats]),
                (B, len(stats))),
        }
    return out
