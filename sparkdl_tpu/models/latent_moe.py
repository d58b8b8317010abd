"""Latent-attention sparse-expert decoder as a prefill-only window scorer —
the sequence models' counterpart of the image zoo (``DeepSequenceScorer``,
``registry.SEQUENCE_MODELS``) — and the pieces the pre-norm stack of the
other sequence models (``models/shortconv_moe.py``) imports from here:
``rms_norm``, the mixed-precision product ``_dot``, ``rotary`` with a kind of
layer's own frequencies (``Rope``), causal attention over grouped keys, with
a span or without (``grouped_causal_attention``: the fused kernel or
``_blocked_attention``), ``gated_mlp``, the expert layer (``route``,
``buffer_capacity``, ``expert_products``, ``routed_experts``,
``expert_stats``), the causal taps of a depthwise convolution
(``causal_taps``) and the scorer's head — the fused kernel or the written
logits' ``log_softmax`` — and counts (``score_head``, ``program_counts``).

One block is: multi-head latent attention (queries and keys/values through
low-rank latents, a rotary part shared by all heads of the key), sandwich
norms (an RMSNorm before AND after each sub-layer), and either a gated MLP
or a sparse-expert layer — sigmoid router over every published expert, the
top-k renormalised and scaled, one shared expert beside the routed ones.

**The expert layer is told which experts it holds** (``experts_held``, the
chip's share under expert parallelism): it routes over all experts, computes
its own experts' part for the (token, expert) pairs routed to them and leaves
out what absent experts would add. On one chip there is no exchange; nothing
here stands in for absent chips. Pairs are sorted by held expert into one
buffer — ``capacity_factor`` × the even share, never more rows than there are
pairs — and **no pair is dropped**: pairs beyond the buffer are computed in
further rounds of the same grouped products, and counted; a layer held whole
runs one round of exactly its pairs.

Precision follows the weights: matrix products run in the weights' dtype
(bfloat16 as the executor ships them) with float32 accumulation; the router,
the norms' statistics, the soft-maxes, the residual stream and the
log-probabilities are float32.

**What runs where.** Everything is XLA's own but three hand-written Pallas TPU
kernels, each one path of two under one contract, the other being XLA's and
the oracle the kernel is tested against. A window's causal attention
(:func:`causal_attention`, and :func:`grouped_causal_attention` for grouped
keys without a separate rotary part, where a span may bound the keys a query
reads): :func:`fused_causal_attention` keeps the scores on the chip and
visits only the key tiles a span reaches; the blocked soft-max, with the
same span, is the other path. The experts' three
grouped products (:func:`expert_products`): :func:`grouped_product`, twice —
gate and up in one pass over the buffer's rows with ``silu · mul`` on the
float32 accumulators, so neither float32 result reaches HBM, then down; three
``lax.ragged_dot`` are the other path. The scorer's head
(:func:`score_head`): :func:`fused_scoring_head` multiplies a block of
positions with a tile of the head's rows at a time and keeps the soft-max's
running maximum and sum and the next id's logit beside it, so no logit
reaches HBM; a window's float32 logits written, ``log_softmax`` and a gather
are the other path. A kernel is taken where the program is
lowered for a TPU — a chip, or an ahead-of-time compile for a described one —
with bfloat16 operands and shapes of whole tiles (attention: a window of
whole query tiles, no longer than a head's keys and values fit on-chip, and
head widths of whole lanes — or, on grouped keys, of half a lane group, 64,
where a grid step works a key head's query heads together, their rows
stacked; the products: a buffer of
whole row tiles and widths of whole lanes; the head: a window of whole blocks
of positions, a ``hidden`` of whole lanes and rows that a tile of whole lanes
divides); a CPU run, float32 weights or
other shapes lower XLA's path. No option chooses, and
``jax.experimental.pallas`` is imported where a kernel is built, not with this
module.

Outputs per window (row): ``pooled`` — the mean over positions of the
final-norm hidden state; ``logprobs`` — ``log p(x[t+1] | x[≤t])`` under the
soft-max over the vocabulary slice held (the last is 0); ``expert_counts`` —
per expert layer and published expert, the tokens of the window routed to it;
and, under ``telemetry.PROGRAM_COUNTS``, the counters the executor records
(among them, per row, the layers whose attention and the expert layers whose
grouped products were lowered to a kernel, and whether the head was).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
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
    topk_eps: float = 0.0     # added to the chosen scores' sum before dividing
    scoring: str = "sigmoid"  # the router's scores: "sigmoid" or "softmax"
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


@dataclass(frozen=True)
class Rope:
    """One kind of layer's rotary as a model's ``rope_parameters`` state it:
    plain (``factor`` 1), or YaRN — the inverse frequencies of the dimensions
    that turn less than ``beta_slow`` times over ``original`` positions
    divided by ``factor``, those that turn more than ``beta_fast`` times left
    alone, a linear ramp between, and ``amplitude`` on cos and sin (the
    scores carry its square)."""

    theta: float
    factor: float = 1.0
    original: int = 0         # positions the model was trained on unscaled
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    amplitude: float = 1.0

    def ramp(self, half):
        """``(low, high)``: the first dimension of a head's ``half`` that is
        scaled at all, and the first scaled in full."""
        def dimension(turns):
            return 2 * half * math.log(
                self.original / (2 * math.pi * turns)) / (
                    2 * math.log(self.theta))

        low = math.floor(dimension(self.beta_fast))
        high = math.ceil(dimension(self.beta_slow))
        return max(low, 0), min(high, 2 * half - 1)

    def frequencies(self, half):
        """(half,) float32 inverse frequencies, from float64."""
        j = np.arange(half, dtype=np.float64)
        plain = self.theta ** (-j / half)
        if self.factor == 1.0:
            return plain.astype(np.float32)
        low, high = self.ramp(half)
        scaled = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
        return (plain * ((1.0 - scaled) + scaled / self.factor)).astype(
            np.float32)


def rotary(x, theta, heads=1, frequencies=None, amplitude=1.0):
    """x (T, heads · rope) float32, head by head as a projection leaves it;
    a head's two halves pair up. The inverse frequencies are ``theta``'s
    plain ones unless handed in ((rope / 2,), :meth:`Rope.frequencies`);
    ``amplitude`` multiplies cos and sin."""
    T = x.shape[0]
    half = x.shape[1] // heads // 2
    t = jnp.arange(T, dtype=jnp.float32)[:, None]
    if frequencies is None:
        frequencies = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = t * frequencies
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    x = x.reshape(T, heads, 2, half)
    a, b = x[:, :, 0], x[:, :, 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], 2).reshape(
        T, -1)


def causal_taps(z, taps, bias=None):
    """A depthwise causal convolution over windows z (B, T, channels)
    float32, zeros before the window: ``c[t] = Σ_j taps[:, j] ⊙ z[t − (L − 1)
    + j]`` with ``taps`` (channels, L), plus ``bias`` (channels,) where given;
    taps and bias float32. The gated short convolution's loop and the
    state-space mixer's."""
    T = z.shape[1]
    taps = taps.astype(jnp.float32)
    L = taps.shape[1]
    z = jnp.pad(z, ((0, 0), (L - 1, 0), (0, 0)))
    c = sum(taps[:, j] * z[:, j:j + T] for j in range(L))
    return c if bias is None else c + bias.astype(jnp.float32)


def _blocked_attention(q, k, v, block, span=None):
    """q (H, T, d), k (G, T, d), v (G, T, dv) → (H, T, dv); q carries the
    scores' scale. Query head a reads key head a // (H / G): with G < H the
    keys are grouped, and a group's H / G query heads meet its one key head
    in one product — keys and values are never copied to the query heads.
    Blocked over queries, each block against its causal prefix of keys only
    — with a ``span``, from the first key of the block's first query's span
    on: query t reads keys t − span < j ≤ t, the earlier ones are not
    multiplied — so the scores of one block are the largest temporary
    (H · block · T float32), not H · T²."""
    H, T, d = q.shape
    G = k.shape[0]
    q = q.reshape(G, H // G, T, d)
    block = min(block, T)
    out = []
    for lo in range(0, T, block):
        hi = min(lo + block, T)
        first = 0 if span is None else max(0, lo - span + 1)
        scores = jnp.einsum("grqd,gkd->grqk", q[:, :, lo:hi], k[:, first:hi],
                            preferred_element_type=jnp.float32)
        keys, queries = jnp.arange(first, hi)[None, :], jnp.arange(
            lo, hi)[:, None]
        mask = keys <= queries
        if span is not None:
            mask &= keys > queries - span
        scores = jnp.where(mask, scores, -jnp.inf)
        weights = jnp.exp(scores - jnp.max(scores, -1, keepdims=True))
        total = jnp.sum(weights, -1, keepdims=True)
        part = jnp.einsum("grqk,gkd->grqd", weights.astype(v.dtype),
                          v[:, first:hi], preferred_element_type=jnp.float32)
        out.append((part / total).astype(v.dtype))
    return jnp.concatenate(out, 2).reshape(H, T, -1)


def scored_keys(window, span, query_tile, key_tile=None):
    """The (query, key) pairs of one head whose scores a path computes over a
    window, masked ones inside a visited tile included: the blocked path
    (``key_tile`` None; ``query_tile`` its block) scores each block of
    queries against every key from its first query's span to its last query;
    the fused kernel visits whole key tiles — those before the block's first
    query that hold a key of some query's span, every row of the block
    against each, then along the diagonal the rows from each tile's first
    on."""
    query_tile = min(query_tile, window)
    total = 0
    for lo in range(0, window, query_tile):
        hi = min(lo + query_tile, window)
        first = 0 if span is None else max(0, lo - span + 1)
        if key_tile is None:
            total += (hi - lo) * (hi - first)
            continue
        total += (hi - lo) * (lo // key_tile - first // key_tile) * key_tile
        total += sum((hi - at) * key_tile for at in range(lo, hi, key_tile))
    return total


# The fused kernel's tiles, chosen from chip runs at 128 heads × 4,096
# positions (PERF.md §6): queries a grid step, keys a tile of scores. A head's
# keys and values stay in on-chip memory whole, which bounds the window: at
# 16,384 positions they are 4 MB each at a width of 128 in bfloat16, 16 MB
# with the pipeline's second buffers, under the 32 MiB the kernel asks for
# (both models' shapes compile for a described v5e). On the chip (PERF.md §6,
# PR 39; one window, 32 query heads on 4 key heads of 128) 16,384 positions
# take 15.3 ms without a span — 144 TFLOP/s over the causal pairs, against
# 4.10 ms at 8,192 and 1.22 at 4,096, and 74.2 on the blocked path — and
# 3.86 ms with a span of 1,024 (the blocked path 4.90). Nothing longer has
# been run: the bound is the longest window measured.
FUSED_QUERY_TILE = 1024
FUSED_KEY_TILE = 512
FUSED_MAX_WINDOW = 16384
# At a head width of half a lane group a grid step works the query heads of
# one key head together, their blocks of queries stacked to one operand of
# rows: the tiles of one head's block, and the most rows a step may stack.
# Chosen from chip runs at 32 query heads on 8 key heads of 64 × 4,096
# positions (PERF.md §6, PR 40; one window's call with XLA's transpositions
# in and out, ms by query tile × key tile): 512 × 256 1.20, 512 × 512 1.30,
# 512 × 128 1.54, 256 × 256 1.36, 256 × 128 1.86, 1,024 × 512 1.27 and
# 1,024 × 256 1.14 — the last stacks 4,096 rows, compiles five times as long
# and is refused for want of on-chip memory with key tiles of 512 at 16,384
# positions, for 0.06 ms — against 4.60 on the blocked path. The kernel
# alone is 0.98 ms of the 1.20, 70 TFLOP/s over the causal pairs where half
# the MXU (a contraction of 64, 64 output columns) is 98.
FUSED_NARROW_QUERY_TILE = 512
FUSED_NARROW_KEY_TILE = 256
FUSED_STACKED_ROWS = 2048
_LANES = 128
_MASKED = -0.7 * float(jnp.finfo(jnp.float32).max)


def _fused_kernel(*refs, query_tile, key_tile, rotary_part, span, stack=1):
    """One head's block of ``query_tile`` queries against its causal prefix,
    a tile of ``key_tile`` keys at a time: the scores, their running maximum
    and sum (kept across all 128 lanes, so no step re-lays them out) and the
    weighted values never leave on-chip memory. With a ``span`` the prefix
    begins at the tile that holds the first key of the block's first query's
    span, and every visited tile is masked to the band. With a ``stack`` the
    rows are that many heads' blocks of the same queries one after the other
    — the query heads of one key head: both products, the maximum and the sum
    run over all of them at once, and a row's place among the queries is its
    place in its own head's block."""
    from jax.experimental import pallas as pl
    if rotary_part:
        qn_ref, qr_ref, kn_ref, kr_ref, v_ref = refs[:5]
    else:
        qn_ref, kn_ref, v_ref = refs[:3]
    out_ref, max_ref, sum_ref, acc_ref = refs[-4:]
    first = pl.program_id(1) * query_tile
    max_ref[...] = jnp.full(max_ref.shape, _MASKED, jnp.float32)
    sum_ref[...] = jnp.zeros(sum_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    transposed = (((1,), (1,)), ((), ()))

    def across(stat, width):
        """A statistic held across the 128 lanes, ``width`` lanes wide."""
        if width % _LANES:
            return stat[:, :width]
        return jnp.tile(stat, (1, width // _LANES))

    def step(lo, start, diagonal):
        """Queries [lo, query_tile) of the block — of every stacked head's,
        as one run of rows where ``lo`` is 0 and a run a head otherwise —
        against the key tile at ``start``; ``diagonal``: the tile begins at
        query ``lo``'s position."""
        runs = [slice(0, stack * query_tile)] if lo == 0 else [
            slice(head * query_tile + lo, (head + 1) * query_tile)
            for head in range(stack)]
        keys = pl.ds(pl.multiple_of(start, key_tile), key_tile)
        for rows in runs:
            scores = lax.dot_general(
                qn_ref[rows, :], kn_ref[keys, :], transposed,
                preferred_element_type=jnp.float32)
            if rotary_part:
                scores = scores + lax.dot_general(
                    qr_ref[rows, :], kr_ref[keys, :], transposed,
                    preferred_element_type=jnp.float32)

            def query():
                """Each row's place among the queries from ``lo`` on."""
                place = lax.broadcasted_iota(jnp.int32, scores.shape, 0)
                if stack > 1 and lo == 0:   # its place in its own head
                    place = lax.rem(place, query_tile)
                return place

            if diagonal:
                scores = jnp.where(
                    lax.broadcasted_iota(jnp.int32, scores.shape, 1)
                    <= query(), scores, _MASKED)
            if span is not None:
                # a key is read where key − query > −span; by their places in
                # the tile, whose first key and query lie start − (first + lo)
                # apart
                scores = jnp.where(
                    lax.broadcasted_iota(jnp.int32, scores.shape, 1)
                    - query() > first + lo - start - span, scores, _MASKED)
            before = max_ref[rows, :]
            highest = jnp.maximum(before, jnp.max(scores, -1, keepdims=True))
            weights = jnp.exp(scores - across(highest, key_tile))
            decay = jnp.exp(before - highest)
            max_ref[rows, :] = highest
            sum_ref[rows, :] = decay * sum_ref[rows, :] + jnp.sum(
                weights, -1, keepdims=True)
            values = v_ref[keys, :]
            acc_ref[rows, :] = across(
                decay, values.shape[1]) * acc_ref[rows, :] + jnp.dot(
                weights.astype(values.dtype), values,
                preferred_element_type=jnp.float32)

    earliest = 0 if span is None else jnp.maximum(
        first - span + 1, 0) // key_tile
    lax.fori_loop(earliest, first // key_tile,
                  lambda tile, _: step(0, tile * key_tile, False), None)
    for lo in range(0, query_tile, key_tile):
        step(lo, first + lo, True)
    out_ref[...] = (acc_ref[...] * across(
        1.0 / sum_ref[...], acc_ref.shape[1])).astype(out_ref.dtype)


def fused_causal_attention(q_nope, q_rope, k_nope, k_rope, v, *, heads=None,
                           span=None, query_tile=None, key_tile=None,
                           interpret=False):
    """:func:`causal_attention`'s contract as one Pallas TPU kernel (an online
    soft-max): only q, k, v and the output cross HBM, each once, in the layout
    the projections leave them in. The two parts of the scores are two
    products, so the rotary key is never copied to every head; the rotary
    queries come heads first because a block cannot cut a head narrower than
    the 128 lanes out of a flat row.

    :func:`grouped_causal_attention`'s contract too: without a rotary part
    (``q_rope`` and ``k_rope`` None, ``heads`` the query heads) the scores are
    the one product, and ``k_nope`` and ``v`` may hold fewer heads than the
    queries — a key head's block is fetched once for the query heads that
    read it, which follow each other on the grid. With a ``span`` only the
    key tiles that hold a key of some query's span are visited.

    **At a head width under the 128 lanes** (64; no rotary part) that block
    cannot be cut out of the flat rows either, so all three operands are
    turned heads first on the way in and the output back on the way out
    (XLA's transpositions, as the blocked path makes them), and a grid step
    is (key head, block of queries): the ``H / G`` query heads that read the
    key head are worked together, their blocks stacked to ``H / G ×
    query_tile`` rows — the MXU sees that many rows in both products and a
    key tile and a value tile are read once for all of them.

    The window is a multiple of ``query_tile``, that of ``key_tile``, and
    ``key_tile`` is a multiple of the 128 lanes; the widths ``nope`` and
    ``v`` are multiples of them or, both, less. The tiles are those chosen on
    the chip for the width unless given."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rotary_part = q_rope is not None
    T = v.shape[0]
    H = q_rope.shape[0] if rotary_part else heads
    nope = q_nope.shape[1] // H
    group = H // (k_nope.shape[1] // nope)      # query heads a key head
    width = v.shape[1] * group // H
    narrow = nope % _LANES != 0
    if query_tile is None:
        query_tile = FUSED_NARROW_QUERY_TILE if narrow else FUSED_QUERY_TILE
    if key_tile is None:
        key_tile = FUSED_NARROW_KEY_TILE if narrow else FUSED_KEY_TILE
    stack = group if narrow else 1
    blocks = T // query_tile

    def key_head(h, i):
        return 0, h if group == 1 else h // group

    if narrow:
        # heads first: (key head, block of queries, the stacked rows, width)
        def heads_first(a):
            return jnp.swapaxes(a.reshape(T, H // stack, -1), 0, 1)

        stacked = jnp.transpose(
            q_nope.reshape(blocks, query_tile, H // stack, stack, nope),
            (2, 0, 3, 1, 4)).reshape(-1, blocks, stack * query_tile, nope)
        operands = (stacked, heads_first(k_nope), heads_first(v))
        in_specs = [pl.BlockSpec((None, None, stack * query_tile, nope),
                                 lambda g, i: (g, i, 0, 0)),
                    pl.BlockSpec((None, T, nope), lambda g, i: (g, 0, 0)),
                    pl.BlockSpec((None, T, width), lambda g, i: (g, 0, 0))]
        out_specs = pl.BlockSpec((None, None, stack * query_tile, width),
                                 lambda g, i: (g, i, 0, 0))
        out_shape = (H // stack, blocks, stack * query_tile, width)
    else:
        rows = pl.BlockSpec((query_tile, nope), lambda h, i: (i, h))
        keys = pl.BlockSpec((T, nope), key_head)
        values = pl.BlockSpec((T, width), key_head)
        if rotary_part:
            rope = q_rope.shape[2]
            in_specs = [rows, pl.BlockSpec((None, query_tile, rope),
                                           lambda h, i: (h, i, 0)),
                        keys, pl.BlockSpec((T, rope), lambda h, i: (0, 0)),
                        values]
            operands = (q_nope, q_rope, k_nope, k_rope, v)
        else:
            in_specs, operands = [rows, keys, values], (q_nope, k_nope, v)
        out_specs = pl.BlockSpec((query_tile, width), lambda h, i: (i, h))
        out_shape = (T, H * width)
    out = pl.pallas_call(
        functools.partial(_fused_kernel, query_tile=query_tile,
                          key_tile=key_tile, rotary_part=rotary_part,
                          span=span, stack=stack),
        grid=(H // stack, blocks),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=jax.ShapeDtypeStruct(out_shape, v.dtype),
        scratch_shapes=[pltpu.VMEM((stack * query_tile, _LANES), jnp.float32),
                        pltpu.VMEM((stack * query_tile, _LANES), jnp.float32),
                        pltpu.VMEM((stack * query_tile, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024),
        name="fused_causal_attention", interpret=interpret,
    )(*operands)
    if narrow:      # back to the flat rows, head by head
        out = jnp.transpose(
            out.reshape(-1, blocks, stack, query_tile, width),
            (1, 3, 0, 2, 4)).reshape(T, H * width)
    return out


def _fused_tiles(operands, window, widths, group=1):
    """What a lowering can see of whether the fused kernel applies, as the
    kernel's ``(query_tile, key_tile)`` or None: bfloat16 operands; head
    widths of whole lanes, or all of half a lane group with the query heads
    of a key head few enough to stack; a window of whole query tiles that a
    head's keys and values hold on-chip."""
    if any(a.dtype != jnp.bfloat16 for a in operands):
        return None
    if all(width % _LANES == 0 for width in widths):
        tiles = FUSED_QUERY_TILE, FUSED_KEY_TILE
    elif (all(2 * width == _LANES for width in widths)
          and group * FUSED_NARROW_QUERY_TILE <= FUSED_STACKED_ROWS):
        tiles = FUSED_NARROW_QUERY_TILE, FUSED_NARROW_KEY_TILE
    else:
        return None
    if window % tiles[0] or window > FUSED_MAX_WINDOW:
        return None
    return tiles


def causal_attention(q_nope, q_rope, k_nope, k_rope, v, block):
    """One window's causal attention. The scores are ``q_nope·k_nopeᵀ +
    q_rope·k_ropeᵀ`` and the queries carry their scale; q_nope, k_nope
    (T, H · nope) and v (T, H · dv) as the projections leave them, head by
    head; q_rope (H, T, rope) rotated, k_rope (T, rope) one head for all.
    Operands in their dtype, scores and soft-max float32, the weights cast to
    v's dtype for the second product, float32 accumulation.

    Returns ``(out (T, H · dv), fused)``. Lowered for a TPU, with bfloat16
    operands and a window and widths that fit the kernel's tiles, this is
    :func:`fused_causal_attention` and ``fused`` is 1; everywhere else the
    blocked path in XLA (queries in blocks of ``block``) and 0. Both come out
    of one ``lax.platform_dependent``, so ``fused`` says what was lowered."""
    H, T, _ = q_rope.shape
    nope, width = q_nope.shape[1] // H, v.shape[1] // H

    def blocked(q_nope, q_rope, k_nope, k_rope, v):
        def heads_first(a):
            return jnp.swapaxes(a.reshape(T, H, -1), 0, 1)

        q = jnp.concatenate([heads_first(q_nope), q_rope], -1)
        k = jnp.concatenate(
            [heads_first(k_nope),
             jnp.broadcast_to(k_rope, (H,) + k_rope.shape)], -1)
        out = _blocked_attention(q, k, heads_first(v), block)
        return jnp.swapaxes(out, 0, 1).reshape(T, H * width), jnp.int32(0)

    def fused(*operands):
        return fused_causal_attention(*operands), jnp.int32(1)

    operands = (q_nope, q_rope, k_nope, k_rope, v)
    if _fused_tiles(operands, T, (nope, width)) is None:
        return blocked(*operands)
    return lax.platform_dependent(*operands, tpu=fused, default=blocked)


def grouped_causal_attention(q, k, v, heads, block, span=None):
    """One window's causal attention over grouped keys: q (T, H · d) and
    k (T, G · d) float32 as rotated, the queries carrying the scores' scale,
    v (T, G · d) in the dtype the products run in; head by head, query head a
    reads key head a // (H / G). With a ``span`` query t reads keys
    t − span < j ≤ t. Precision as :func:`causal_attention`'s.

    Returns ``(out (T, H · d) in v's dtype, fused, scored)``: ``fused`` as
    :func:`causal_attention` returns it — the kernel where the program is
    lowered for a TPU with bfloat16 values, a window of whole query tiles
    and a head width of whole lanes, or of 64 with no more query heads a key
    head than a grid step stacks (:func:`_fused_tiles`); the blocked path
    everywhere else; either with the same span — and ``scored`` () int32 the
    (query, key) pairs of one head whose scores that path computes
    (:func:`scored_keys`, of the tiles the kernel takes at that width), out
    of the same ``lax.platform_dependent``."""
    T = q.shape[0]
    width = q.shape[1] // heads

    def blocked(q, k, v):
        def heads_first(a):
            return jnp.swapaxes(a.reshape(T, -1, width), 0, 1).astype(
                v.dtype)

        out = _blocked_attention(heads_first(q), heads_first(k),
                                 heads_first(v), block, span)
        return (jnp.swapaxes(out, 0, 1).reshape(T, -1), jnp.int32(0),
                jnp.int32(scored_keys(T, span, block)))

    def fused(q, k, v):
        out = fused_causal_attention(q.astype(v.dtype), None,
                                     k.astype(v.dtype), None, v, heads=heads,
                                     span=span)
        return out, jnp.int32(1), jnp.int32(scored_keys(T, span, *tiles))

    tiles = _fused_tiles((v,), T, (width,), heads * width // k.shape[1])
    if tiles is None:
        return blocked(q, k, v)
    return lax.platform_dependent(q, k, v, tpu=fused, default=blocked)


def latent_attention(p, x, c: LatentMoEConfig):
    """x (T, hidden) float32, one window → ``((T, hidden) float32, fused)``,
    ``fused`` as :func:`causal_attention` returns it. Each part of q, k and v
    is its own product of the latents with its columns of the up-projection,
    so it comes out in the layout the attention reads."""
    T = x.shape[0]
    act = p["q_up"].dtype
    width = c.nope + c.rope
    # the scores' scale is linear through q_up and the rotation: in the
    # float32 latent it costs the queries no second rounding
    cq = rms_norm(_dot(x, p["q_down"]), p["q_norm"], c.eps) * width ** -0.5
    down = _dot(x, p["kv_down"])
    ckv = rms_norm(down[:, :c.kv_rank], p["kv_norm"], c.eps)
    k_rope = rotary(down[:, c.kv_rank:], c.theta).astype(act)

    def part(latent, w, lo, hi):
        """The latent times columns lo:hi of every head's group of w."""
        columns = w.reshape(w.shape[0], c.heads, -1)[:, :, lo:hi]
        return _dot(latent, columns.reshape(w.shape[0], -1), act)

    q_rope = rotary(part(cq, p["q_up"], c.nope, width).astype(jnp.float32),
                    c.theta, c.heads).astype(act)
    q_rope = jnp.swapaxes(q_rope.reshape(T, c.heads, c.rope), 0, 1)
    out, fused = causal_attention(
        part(cq, p["q_up"], 0, c.nope), q_rope,
        part(ckv, p["kv_up"], 0, c.nope), k_rope,
        part(ckv, p["kv_up"], c.nope, c.nope + c.v), c.query_block)
    return _dot(out, p["out"]), fused


def gated_mlp(p, x):
    act = p["down"].dtype
    hidden = jax.nn.silu(_dot(x, p["gate"])) * _dot(x, p["up"])
    return _dot(hidden.astype(act), p["down"])


def route(p, x, c):
    """Float32 at full precision: (chosen ids (N, k), weights (N, k)). The
    scores are the logits' sigmoids, each expert by itself, or their soft-max
    over all published experts (``c.scoring``). Where the layer holds a
    selection bias (``"expert_bias"``), the top-k is taken of ``scores +
    bias`` and the weights are the unbiased scores of the chosen."""
    score = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[c.scoring]
    scores = score(jnp.dot(x.astype(jnp.float32),
                           p["router"].astype(jnp.float32),
                           precision=lax.Precision.HIGHEST))
    if "expert_bias" in p:
        _, chosen = lax.top_k(scores + p["expert_bias"].astype(jnp.float32),
                              c.top_k)
        top = jnp.take_along_axis(scores, chosen, -1)
    else:
        top, chosen = lax.top_k(scores, c.top_k)
    if c.norm_topk:
        top = top / (jnp.sum(top, -1, keepdims=True) + c.topk_eps)
    return chosen, top * c.scaling


# float32 rows the combine's gathers may hold at once (see routed_experts)
COMBINE_BYTES = 128 * 1024 * 1024

# The grouped products' tiles, chosen from chip runs at 65,536 rows in 32
# groups, 2,048 × 1,792, and at 16,384 rows in 16 groups, 7,680 × 2,048
# (PERF.md §6, PR 38). Rows a grid step, worked in sub-tiles of which a visit
# skips those without a row of its group: every boundary between two groups
# costs a second visit of its tile, and what that visit multiplies is what
# counts — with whole tiles 1,024 / 512 / 256 rows took 12.1 / 9.4 / 8.7 ms
# for the three products, in sub-tiles of 128 a tile of 512 takes 8.3. The
# whole contraction in one step; the columns as wide as the blocks' budget of
# on-chip memory allows (all 1,792 / 2,048 of LFM2's, 512 of openPangu's 2,048
# and 2,560 of its 7,680: blocks under 48 MiB; at 72 MB the kernel ran twice
# as slow, though v5e has 128 MiB).
GROUPED_ROW_TILE = 512
GROUPED_SUB_TILE = 128
GROUPED_BLOCK_BYTES = 48 * 1024 * 1024
GROUPED_VMEM_LIMIT = 100 * 1024 * 1024


def _group_visits(sizes, rows, row_tile):
    """The grouped kernel's grid over its rows: one visit for each (row tile,
    group) that share a row, group by group, so a tile that straddles a
    boundary is visited once for each group it touches, one after the other;
    and one visit, by a group of no rows, for each whole tile past the last
    group's end, which the kernel has to zero. ``sizes`` (groups,) int32 may
    be traced; ``rows`` is a multiple of ``row_tile``. Returns ``(group_of,
    tile_of (rows / row_tile + groups − 1,) per visit, offsets (groups + 2,)
    rows before each group, visits ())``."""
    groups = sizes.shape[0]
    tiles = rows // row_tile
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    covered = -(-ends[-1] // row_tile)          # tiles that hold a group's row
    first = jnp.append(starts // row_tile, covered)
    count = jnp.append(
        jnp.where(sizes > 0, (ends - 1) // row_tile - starts // row_tile + 1,
                  0), tiles - covered)
    bound = tiles + groups - 1
    group_of = jnp.repeat(jnp.arange(groups + 1, dtype=jnp.int32), count,
                          total_repeat_length=bound)
    tile_of = first[group_of] + jnp.arange(bound, dtype=jnp.int32) - (
        jnp.cumsum(count) - count)[group_of]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends, ends[-1:]])
    return group_of, jnp.clip(tile_of, 0, tiles - 1), offsets, jnp.sum(count)


def _grouped_kernel(group_of, tile_of, offsets, rows_ref, *refs, row_tile,
                    sub_tile):
    """One visit of :func:`_group_visits`: the tile's rows times the group's
    one weight block (``out = rows · w``) or two (``out = silu(rows · gate) ·
    (rows · up)``, on the float32 accumulators), ``sub_tile`` rows at a time
    in a loop — one product's code whatever the tile: only the sub-tiles that
    hold a row of the group are multiplied, and written to the rows that are
    the group's; the tile's other rows are left as the visit before wrote
    them, or zeroed on the tile's first visit."""
    from jax.experimental import pallas as pl
    weights, out_ref = refs[:-1], refs[-1]
    visit = pl.program_id(1)
    group, tile = group_of[visit], tile_of[visit]
    lo, hi = offsets[group], offsets[group + 1]
    first = tile * row_tile
    revisit = (visit > 0) & (tile_of[jnp.maximum(visit - 1, 0)] == tile)

    def part(i, _):
        at = pl.multiple_of(i * sub_tile, sub_tile)
        rows = pl.ds(at, sub_tile)
        shared = (lo < first + at + sub_tile) & (hi > first + at)

        @pl.when(shared)
        def _():
            acc = [jnp.dot(rows_ref[rows, :], w[...],
                           preferred_element_type=jnp.float32)
                   for w in weights]
            acc = jax.nn.silu(acc[0]) * acc[1] if len(acc) == 2 else acc[0]
            row = first + at + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
            before = jnp.where(
                revisit, out_ref[rows, :].astype(jnp.float32), 0.0)
            out_ref[rows, :] = jnp.where((row >= lo) & (row < hi), acc, before
                                         ).astype(out_ref.dtype)

        @pl.when(jnp.logical_not(shared | revisit))
        def _():
            out_ref[rows, :] = jnp.zeros((sub_tile, out_ref.shape[1]),
                                         out_ref.dtype)

    lax.fori_loop(0, row_tile // sub_tile, part, None)


def _column_tile(contraction, columns, operands, row_tile, out_bytes):
    """The widest tile of whole lanes that divides ``columns`` and keeps a
    grid step's blocks — rows, ``operands`` weight blocks and the output,
    each twice for the pipeline — within ``GROUPED_BLOCK_BYTES``."""
    for parts in range(1, columns // _LANES + 1):
        tile, rest = divmod(columns, parts)
        if rest or tile % _LANES:
            continue
        blocks = 2 * (row_tile * contraction * 2
                      + operands * contraction * tile * 2
                      + row_tile * tile * out_bytes)
        if blocks <= GROUPED_BLOCK_BYTES:
            return tile
    return None


@functools.partial(jax.jit, static_argnames=(
    "out_dtype", "row_tile", "sub_tile", "interpret"))
def grouped_product(rows, weights, sizes, out_dtype, *,
                    row_tile=GROUPED_ROW_TILE, sub_tile=GROUPED_SUB_TILE,
                    interpret=False):
    """The grouped product as one Pallas TPU kernel (the algorithm of jax's
    ``megablox.gmm``): ``rows`` (R, K) sorted by group, ``weights`` one array
    (groups, K, N) or two — ``rows · w[g]``, or ``silu(rows · gate[g]) ·
    (rows · up[g])`` with both products and the epilogue on float32
    accumulators on the chip — and ``sizes`` (groups,) int32 each group's
    rows, which may be traced. Operands in their dtype, float32 accumulation,
    one rounding to ``out_dtype``. The rows are read once a column tile and a
    group's weights once a run of visits; **rows past the last group's end
    come out zero**, as ``lax.ragged_dot`` leaves them on a CPU (the combine
    multiplies them by a weight of nought, and nought times an unwritten row
    need not be nought). ``R`` is a multiple of ``row_tile``, that of
    ``sub_tile``, and ``K`` and ``N`` are multiples of the 128 lanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    groups, contraction, columns = weights[0].shape
    tile = _column_tile(contraction, columns, len(weights), row_tile,
                        jnp.dtype(out_dtype).itemsize)
    group_of, tile_of, offsets, visits = _group_visits(
        sizes, rows.shape[0], row_tile)

    def weight_block(n, v, group_of, tile_of, offsets):
        return jnp.minimum(group_of[v], groups - 1), 0, n

    return pl.pallas_call(
        functools.partial(_grouped_kernel, row_tile=row_tile,
                          sub_tile=min(sub_tile, row_tile)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(columns // tile, visits),
            in_specs=[pl.BlockSpec(
                (row_tile, contraction),
                lambda n, v, group_of, tile_of, offsets: (tile_of[v], 0))]
            + [pl.BlockSpec((None, contraction, tile), weight_block)
               for _ in weights],
            out_specs=pl.BlockSpec(
                (row_tile, tile),
                lambda n, v, group_of, tile_of, offsets: (tile_of[v], n))),
        out_shape=jax.ShapeDtypeStruct((rows.shape[0], columns), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=GROUPED_VMEM_LIMIT),
        name="grouped_product", interpret=interpret,
    )(group_of, tile_of, offsets, rows, *weights)


def expert_products(rows, experts, sizes):
    """The experts' three grouped products over a buffer sorted by expert:
    ``silu(rows · gate[g]) · (rows · up[g])``, rounded once to the weights'
    dtype, times ``down[g]``; ``rows`` (R, hidden) in that dtype, ``sizes``
    (held,) int32 each expert's rows (it may be traced), float32 accumulation
    and a float32 result, zero past the last expert's rows.

    Returns ``(out (R, hidden) float32, fused)``. Lowered for a TPU, with
    bfloat16 operands, a buffer of whole row tiles and widths of whole lanes,
    this is :func:`grouped_product` twice — gate and up in one pass over the
    rows, their float32 results never in HBM — and ``fused`` is 1; everywhere
    else three ``lax.ragged_dot`` and 0. Both come out of one
    ``lax.platform_dependent``, so ``fused`` says what was lowered."""
    def ragged(rows, gate, up, down, sizes):
        hidden = jax.nn.silu(lax.ragged_dot(
            rows, gate, sizes, preferred_element_type=jnp.float32)
            ) * lax.ragged_dot(rows, up, sizes,
                               preferred_element_type=jnp.float32)
        return lax.ragged_dot(hidden.astype(rows.dtype), down, sizes,
                              preferred_element_type=jnp.float32
                              ), jnp.int32(0)

    def kernels(rows, gate, up, down, sizes):
        hidden = grouped_product(rows, (gate, up), sizes, rows.dtype)
        return grouped_product(hidden, (down,), sizes, jnp.float32
                               ), jnp.int32(1)

    operands = (rows, experts["gate"], experts["up"], experts["down"], sizes)
    _, hidden, width = experts["gate"].shape
    fits = (all(a.dtype == jnp.bfloat16 for a in operands[:4])
            and rows.shape[0] % GROUPED_ROW_TILE == 0
            and hidden % _LANES == 0 and width % _LANES == 0
            and _column_tile(hidden, width, 2, GROUPED_ROW_TILE, 2)
            and _column_tile(width, hidden, 1, GROUPED_ROW_TILE, 4))
    if not fits:
        return ragged(*operands)
    return lax.platform_dependent(*operands, tpu=kernels, default=ragged)


def buffer_capacity(tokens, c):
    """Rows of the grouped products' buffer for a launch of ``tokens``:
    ``capacity_factor`` × the pairs that meet a held expert when routing is
    even, and never more than the pairs there are — a layer held whole runs
    one round of exactly its pairs."""
    even = c.capacity_factor * tokens * c.top_k * len(c.experts_held) \
        / c.experts
    return min(max(8, -(-int(even) // 8) * 8), tokens * c.top_k)


def routed_experts(p, x, c):
    """The held experts' part of the layer for tokens x (N, hidden) float32;
    ``c`` either sequence model's configuration.

    The (token, expert) pairs that meet a held expert are sorted by expert
    into one flat buffer of :func:`buffer_capacity` rows; a round gathers the
    buffer's tokens and runs the three grouped products
    (:func:`expert_products`, group sizes = each expert's pairs in the
    buffer). Pairs beyond the buffer take further rounds: none is dropped; a
    layer held whole has a row for every pair and runs one round without the
    loop.

    **The products are a kernel on a TPU.** On the chip (PERF.md §6, PR 38;
    the three products alone) they take 8.3 ms where a layer of 32 experts is
    held whole (65,536 rows, 2,048 × 1,792: gate and up in one pass 5.5 ms,
    down 3.0, 173 TFLOP/s) against 19.6 ms as three ``lax.ragged_dot`` with
    ``silu · mul`` between them (73 TFLOP/s, and 1.9 GB of float32 gate and up
    through HBM), and 6.8 ms where 16 of 256 are held (a buffer of 16,384 rows
    × 7,680 that routing fills by half: a tile without a pair is zeroed, not
    multiplied) against 10.3.

    **The combine is linear in the pairs.** The sort is a permutation, so its
    inverse says where each token's ``top_k`` results lie in the buffer: a
    round gathers them, a tile of tokens at a time and one gather for each of
    the ``top_k``, weighs each by its router weight — nought where the expert
    is absent or the pair lies in another round — and adds them, in float32.
    On the chip (PERF.md §6, PR 37; the layer alone, 16,384 tokens) the
    combine takes 2.3 ms where a layer of 32 experts is held whole (65,536
    pairs of width 2,048; the one-hot product tokens × buffer on the MXU,
    which this replaced, 24–26 ms, whole or in token tiles) and 10.4 ms where
    16 of 256 are held (131,072 pairs of width 7,680, most of them absent;
    the one-hot product 23.0 ms, a row scatter-add 47 ms). The tiles bound
    what is alive at once, and are no slower: untiled the same gathers took
    3.0 and 18.5 ms and held 3.5 GiB more in the second case.

    Returns ``(y (N, hidden) float32, chosen (N, k), counts (held,) pairs per
    held expert, overflow (N·k,) bool per pair: computed beyond the first
    round, fused () int32 as :func:`expert_products` returns it)``."""
    N = x.shape[0]
    held = len(c.experts_held)
    chosen, weights = route(p, x, c)
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
    capacity = buffer_capacity(N, c)
    # where each pair lies in the sorted order, and its weight if held
    place = jnp.zeros((pairs,), jnp.int32).at[order].set(
        jnp.arange(pairs, dtype=jnp.int32))
    held_weights = jnp.where(place < local, weights.reshape(-1), 0.0)
    experts = p["experts"]
    act = experts["down"].dtype
    xa = x.astype(act)
    # the gathers of a tile of tokens are alive side by side: in tiles, so
    # that they stay under COMBINE_BYTES (whole, a launch of 16,384 tokens of
    # width 7,680 with 8 pairs each held 3.5 GiB of them)
    tiles = 1
    while (pairs * x.shape[1] * 4 > COMBINE_BYTES * tiles
           and N % (2 * tiles) == 0):
        tiles *= 2

    def one_round(r, carry):
        y, _ = carry
        lo = r * capacity
        pair = order[jnp.clip(lo + jnp.arange(capacity), 0, pairs - 1)]
        rows = xa[pair // c.top_k]                         # (capacity, hidden)
        sizes = (jnp.clip(ends - lo, 0, capacity)
                 - jnp.clip(starts - lo, 0, capacity)).astype(jnp.int32)
        out, fused = expert_products(rows, experts, sizes)
        at = (place - lo).reshape(tiles, -1, c.top_k)
        mine = jnp.where((at >= 0) & (at < capacity),
                         held_weights.reshape(at.shape), 0.0)
        at = jnp.clip(at, 0, capacity - 1)

        def add_tile(i, y):
            first = i * (N // tiles)
            tile = lax.dynamic_slice_in_dim(y, first, N // tiles) + sum(
                out[at[i, :, k]] * mine[i, :, k, None]
                for k in range(c.top_k))
            return lax.dynamic_update_slice_in_dim(y, tile, first, 0)

        return lax.fori_loop(0, tiles, add_tile, y), fused

    none = jnp.zeros(x.shape, jnp.float32), jnp.int32(0)
    if capacity == pairs:
        y, fused = one_round(0, none)
    else:       # rounds: 1 unless the held experts are full
        y, fused = lax.fori_loop(0, -(-local // capacity), one_round, none)
    overflow = jnp.zeros((pairs,), bool).at[order].set(
        (slots[order] < held) & (jnp.arange(pairs) >= capacity))
    return y, chosen, counts, overflow, fused


def expert_stats(chosen, counts, overflow, fused, rows, c):
    """What an expert layer reports of a launch of ``rows`` windows, from
    :func:`routed_experts`' returns: per row the tokens per published expert,
    the pairs that met a held expert, those computed beyond the first round,
    its share of the rows the grouped products ran (rounds × the buffer), and
    whether those products were lowered to the kernel; of the launch, the
    fullest held expert's pairs over the mean."""
    held = jnp.asarray(c.experts_held, jnp.int32)
    per_row = jnp.sum(chosen.reshape(rows, -1, 1) == jnp.arange(c.experts), 1)
    capacity = buffer_capacity(chosen.shape[0], c)
    ran = (-(-jnp.sum(counts) // capacity) * capacity).astype(jnp.int32)
    return {
        "expert_counts": per_row.astype(jnp.int32),              # (B, experts)
        "local_pairs": jnp.sum(per_row[:, held], -1).astype(jnp.int32),
        "overflow_pairs": jnp.sum(overflow.reshape(rows, -1), -1
                                  ).astype(jnp.int32),
        # a whole number a row, and the rows' sum is the launch's
        "buffer_rows": ran // rows + (jnp.arange(rows) < ran % rows),
        "fused_products": jnp.broadcast_to(fused, (rows,)),
        "load_max_over_mean": jnp.max(counts) / jnp.maximum(
            jnp.mean(counts.astype(jnp.float32)), 1.0),
    }


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
                layer["attn"], rms_norm(row, layer["input_norm"], c.eps), c),
            h)
    h = h + rms_norm(attended, layer["post_attn_norm"], c.eps)
    x = rms_norm(h, layer["pre_mlp_norm"], c.eps)
    if "moe" not in layer:
        m = lax.map(lambda row: gated_mlp(layer["mlp"], row), x)
        return h + rms_norm(m, layer["post_mlp_norm"], c.eps), fused, None
    moe = layer["moe"]
    flat = x.reshape(B * T, -1)
    with jax.named_scope("routed_experts"):
        routed, *told = routed_experts(moe, flat, c)
    m = (gated_mlp(moe["shared"], flat) + routed).reshape(B, T, -1)
    return (h + rms_norm(m, layer["post_mlp_norm"], c.eps), fused,
            expert_stats(*told, B, c))


# float32 logits (positions × rows of the head) a window's head may hold at
# once on the path that writes them: LFM2's 4,096 × 65,536 are whole at this
# bound; 16,384 × 98,304 (6.4 GB) go in eight blocks of positions
HEAD_LOGITS_BYTES = 1 << 30

# The fused head's blocks: positions a grid step — each block reads the whole
# head once, so the product's FLOPs a byte of the head are the block's
# positions, and under 256 (the chip's 197 TFLOP/s over its 819 GB/s is 240)
# the head's bytes would bound it —, the most rows of the head a tile of
# logits, and what a step's blocks — the positions and the tile, each twice
# for the pipeline, and the tile's float32 logits — may take of on-chip
# memory (the kernel asks for the grouped products' ``GROUPED_VMEM_LIMIT``).
HEAD_POSITION_BLOCKS = (1024, 512, 256)
HEAD_ROW_TILE = 512
HEAD_BLOCK_BYTES = 48 * 1024 * 1024


def _head_kernel(x_ref, head_ref, following_ref, out_ref, max_ref, sum_ref,
                 hit_ref, *, tile):
    """One block of positions against one tile of the head's rows: the
    tile's logits live here only. Each of the 128 lanes keeps the running
    maximum, the sum of exponentials under it and the next id's logit of
    the columns that fall on it (a column's lane is its id modulo 128), so a
    step reduces nothing across lanes; the last tile's step folds the lanes
    into ``logit[next id] − logsumexp(logits)``, one float32 a position."""
    from jax.experimental import pallas as pl
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _():
        max_ref[...] = jnp.full(max_ref.shape, _MASKED, jnp.float32)
        sum_ref[...] = jnp.zeros(sum_ref.shape, jnp.float32)
        hit_ref[...] = jnp.zeros(hit_ref.shape, jnp.float32)

    logits = lax.dot_general(x_ref[...], head_ref[...],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    parts = [logits[:, at:at + _LANES] for at in range(0, tile, _LANES)]
    before = max_ref[...]
    highest = functools.reduce(jnp.maximum, parts, before)
    # the next id's place in this tile, were it here: lane + a part's first
    place = following_ref[...] - step * tile - lax.broadcasted_iota(
        jnp.int32, before.shape, 1)
    sum_ref[...] = sum_ref[...] * jnp.exp(before - highest) + sum(
        jnp.exp(part - highest) for part in parts)
    hit_ref[...] = hit_ref[...] + sum(
        jnp.where(place == at, part, 0.0)
        for at, part in zip(range(0, tile, _LANES), parts))
    max_ref[...] = highest

    @pl.when(step == pl.num_programs(1) - 1)
    def _():
        top = jnp.max(highest, -1, keepdims=True)
        total = jnp.sum(sum_ref[...] * jnp.exp(highest - top), -1,
                        keepdims=True)
        out_ref[...] = jnp.sum(hit_ref[...], -1, keepdims=True) - (
            top + jnp.log(total))


def fused_scoring_head(x, head, following, *, block, tile, interpret=False):
    """The scorer's head as one Pallas TPU kernel (an online soft-max over
    the head's rows): ``x`` (N, hidden) positions and ``head`` (rows, hidden)
    as it lies — the product contracts both operands' last axis, so a tied
    head is the embedding itself — in one dtype, ``following`` (N,) int32 the
    id after each position → (N,) float32 ``logit[following] −
    logsumexp(logits)`` with ``logits = x · headᵀ`` accumulated in float32.
    No logit reaches HBM: only ``x`` once, the head once a block of
    positions, and one number a position cross it. A ``following`` outside
    the rows meets no column: its logit reads 0.

    ``N`` is a multiple of ``block``, ``rows`` of ``tile`` and ``tile`` of
    the 128 lanes (:func:`_head_blocks` chooses both)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    positions, hidden = x.shape
    rows = head.shape[0]
    out = pl.pallas_call(
        functools.partial(_head_kernel, tile=tile),
        grid=(positions // block, rows // tile),
        in_specs=[pl.BlockSpec((block, hidden), lambda i, j: (i, 0)),
                  pl.BlockSpec((tile, hidden), lambda i, j: (j, 0)),
                  pl.BlockSpec((block, 1), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((block, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((positions, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=GROUPED_VMEM_LIMIT),
        name="fused_scoring_head", interpret=interpret,
    )(x, head, following.reshape(-1, 1))
    return out[:, 0]


def _head_blocks(window, hidden, rows, dtype):
    """What a lowering can see of whether the fused head applies, as the
    kernel's ``(block, tile)`` or None: a bfloat16 head of whole lanes of
    ``hidden``; the most positions a step that divide the window, and with
    them the most rows of the head a tile — whole lanes that divide the
    rows — whose blocks stay within ``HEAD_BLOCK_BYTES``."""
    if dtype != jnp.bfloat16 or hidden % _LANES:
        return None
    for block in HEAD_POSITION_BLOCKS:
        if window % block:
            continue
        for tile in range(HEAD_ROW_TILE, 0, -_LANES):
            if rows % tile == 0 and (4 * hidden * (block + tile)
                                     + 4 * block * tile <= HEAD_BLOCK_BYTES):
                return block, tile
    return None


def score_head(params, h, tokens, eps):
    """The scorer's head over windows h (B, T, hidden) float32 after the last
    block: ``pooled``, the mean over positions of the final-norm state, and
    ``logprobs``, ``log p(x[t+1] | x[≤t])`` under the soft-max over the rows
    of ``head`` (the last 0). An id outside the rows held would be clamped by
    the lookups: its window's outputs are not a number instead.

    Returns ``(outputs, fused)``, ``fused`` (B,) int32. Lowered for a TPU,
    with a bfloat16 head and a window and widths that :func:`_head_blocks`
    finds blocks for, the log-probabilities are
    :func:`fused_scoring_head`'s over all windows' positions — the product,
    the soft-max's maximum and sum and the next id's logit in one kernel, no
    logit in HBM — and ``fused`` is 1; everywhere else XLA's path, a window
    at a time, writes the float32 logits (its positions in blocks where a
    window's would pass ``HEAD_LOGITS_BYTES``), takes ``log_softmax`` and
    gathers, and 0. Both come out of one ``lax.platform_dependent``, so
    ``fused`` says what was lowered."""
    with jax.named_scope("head"):
        x = rms_norm(h, params["final_norm"], eps)
        (B, T), (rows, hidden) = tokens.shape, params["head"].shape
        tiles = _head_blocks(T, hidden, rows, params["head"].dtype)

        def written(x, tokens, head):
            blocks = 1
            while T * rows * 4 > HEAD_LOGITS_BYTES * blocks and T % (
                    2 * blocks) == 0:
                blocks *= 2

            def logits_of(part):
                return jnp.dot(part.astype(head.dtype), head.T,
                               preferred_element_type=jnp.float32)

            def row_logprobs(args):
                row, ids = args
                logp = jax.nn.log_softmax(logits_of(row), -1)
                nxt = jnp.take_along_axis(logp[:-1], ids[1:, None], -1)[:, 0]
                return jnp.pad(nxt, (0, 1))

            def row_logprobs_in_blocks(args):
                """The same, ``blocks`` blocks of positions one after the
                other: a window's float32 logits are never whole."""
                row, ids = args

                def block_logprobs(args):
                    part, following = args
                    return jnp.take_along_axis(
                        jax.nn.log_softmax(logits_of(part), -1), following,
                        -1)[:, 0]

                nxt = lax.map(block_logprobs, (
                    row.reshape(blocks, -1, row.shape[-1]),
                    jnp.pad(ids[1:], (0, 1)).reshape(blocks, -1, 1)))
                return nxt.reshape(-1).at[-1].set(0.0)

            if blocks > 1:
                row_logprobs = row_logprobs_in_blocks
            return lax.map(row_logprobs, (x, tokens)), jnp.int32(0)

        def fused(x, tokens, head):
            block, tile = tiles
            following = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
            nxt = fused_scoring_head(
                x.astype(head.dtype).reshape(B * T, hidden), head,
                following.reshape(-1), block=block, tile=tile)
            return nxt.reshape(B, T).at[:, -1].set(0.0), jnp.int32(1)

        operands = (x, tokens, params["head"])
        if tiles is None:
            logprobs, engaged = written(*operands)
        else:
            logprobs, engaged = lax.platform_dependent(
                *operands, tpu=fused, default=written)
        known = jnp.all((tokens >= 0) & (tokens < params["embed"].shape[0]),
                        1)
        return {"pooled": jnp.where(known[:, None], jnp.mean(x, 1), jnp.nan),
                "logprobs": jnp.where(known[:, None], logprobs, jnp.nan)
                }, jnp.broadcast_to(engaged, (B,))


def program_counts(stats, tokens, counts):
    """What a scorer's outputs gain beside ``pooled`` and ``logprobs``: under
    ``telemetry.PROGRAM_COUNTS`` the tokens scored and the model's own
    ``counts`` (name → (B,) int32), from every stack; and from one with
    expert layers — ``stats`` their :func:`expert_stats`, one a layer —
    ``expert_counts`` and the expert layers' counts beside them."""
    B, T = tokens.shape

    def stacked(name):
        return jnp.stack([s[name] for s in stats], 1)

    out = {"expert_counts": stacked("expert_counts")} if stats else {}
    program = {telemetry.M_SEQUENCE_TOKENS: jnp.full((B,), T, jnp.int32),
               **counts}
    if stats:
        program.update({
            telemetry.M_MOE_ROUTED_TOKENS: jnp.full(
                (B,), T * len(stats), jnp.int32),
            telemetry.M_MOE_LOCAL_PAIRS: jnp.sum(stacked("local_pairs"), 1),
            telemetry.M_MOE_OVERFLOW_PAIRS: jnp.sum(
                stacked("overflow_pairs"), 1),
            telemetry.M_MOE_BUFFER_ROWS: jnp.sum(stacked("buffer_rows"), 1),
            telemetry.M_MOE_FUSED_PRODUCT_LAYERS: jnp.sum(
                stacked("fused_products"), 1),
            telemetry.M_MOE_LOAD_MAX_OVER_MEAN: jnp.broadcast_to(
                jnp.stack([s["load_max_over_mean"] for s in stats]),
                (B, len(stats)))})
    return {**out, telemetry.PROGRAM_COUNTS: program}


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
