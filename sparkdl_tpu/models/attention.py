"""Causal attention over one window, the one attention mechanism of the
sequence stacks: the latent model's, whose scores have a rotary part shared
by all heads of the key (:func:`causal_attention`), and attention over
grouped keys — fewer key heads than query heads — with a span or without
(:func:`grouped_causal_attention`); each after a kind of layer's rotary
(:class:`Rope`, :func:`rotary`: plain, or YaRN's scaled frequencies and
amplitude, over a whole head or its leading share).

**Two paths under one contract**, chosen where the program is lowered (one
``lax.platform_dependent``, never at run time): lowered for a TPU — a chip,
or an ahead-of-time compile for a described one — with the dtype and shapes
the kernel's tiles take (:func:`_fused_tiles`), :func:`fused_causal_attention`,
one Pallas TPU kernel that keeps the scores on the chip and visits only the
key tiles a span reaches; everywhere else the blocked soft-max
(:func:`_blocked_attention`) with the same span, the kernel's oracle. Both
return ``fused``, 1 where the kernel was lowered. No option chooses, and
``jax.experimental.pallas`` is imported where the kernel is built.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from sparkdl_tpu.models.sequence_ops import LANES, MASKED, mixed_dot, rms_norm


@dataclass(frozen=True)
class Rope:
    """One kind of layer's rotary as a model's ``rope_parameters`` state it:
    plain (``factor`` 1), or YaRN — the inverse frequencies of the dimensions
    that turn less than ``beta_slow`` times over ``original`` positions
    divided by ``factor``, those that turn more than ``beta_fast`` times left
    alone, a linear ramp between, and ``amplitude`` on cos and sin (the
    scores carry its square). ``partial`` is the share of a head that turns
    (``partial_rotary_factor``): its first dimensions, over which the
    frequencies and the ramp are; the rest pass through. ``interleave``
    (``rope_interleave``) pairs adjacent dimensions ``(2j, 2j + 1)`` where
    the plain rotary pairs a head's two halves ``(j, j + r/2)``."""

    theta: float
    factor: float = 1.0
    original: int = 0         # positions the model was trained on unscaled
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    amplitude: float = 1.0
    partial: float = 1.0
    interleave: bool = False

    def turned(self, head_dim):
        """The leading dimensions of a head of ``head_dim`` that turn."""
        return int(head_dim * self.partial)

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


def rotary(x, theta, heads=1, frequencies=None, amplitude=1.0,
           interleave=False):
    """x (T, heads · rope) float32, head by head as a projection leaves it;
    a head's two halves pair up, or with ``interleave`` its adjacent
    dimensions, pair j turning at the j-th frequency either way. The inverse
    frequencies are ``theta``'s plain ones unless handed in ((rope / 2,),
    :meth:`Rope.frequencies`); ``amplitude`` multiplies cos and sin."""
    T = x.shape[0]
    half = x.shape[1] // heads // 2
    t = jnp.arange(T, dtype=jnp.float32)[:, None]
    if frequencies is None:
        frequencies = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = t * frequencies
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    if interleave:
        x = x.reshape(T, heads, half, 2)
        a, b = x[..., 0], x[..., 1]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin], 3).reshape(
            T, -1)
    x = x.reshape(T, heads, 2, half)
    a, b = x[:, :, 0], x[:, :, 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], 2).reshape(
        T, -1)


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
# (both models' shapes compile for a described v5e). On the chip (PERF.md §6:
# one window, 32 query heads on 4 key heads of 128) 16,384 positions
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
# positions (PERF.md §6: one window's call with XLA's transpositions
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
    max_ref[...] = jnp.full(max_ref.shape, MASKED, jnp.float32)
    sum_ref[...] = jnp.zeros(sum_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    transposed = (((1,), (1,)), ((), ()))

    def across(stat, width):
        """A statistic held across the 128 lanes, ``width`` lanes wide."""
        if width % LANES:
            return stat[:, :width]
        return jnp.tile(stat, (1, width // LANES))

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
                    <= query(), scores, MASKED)
            if span is not None:
                # a key is read where key − query > −span; by their places in
                # the tile, whose first key and query lie start − (first + lo)
                # apart
                scores = jnp.where(
                    lax.broadcasted_iota(jnp.int32, scores.shape, 1)
                    - query() > first + lo - start - span, scores, MASKED)
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
    narrow = nope % LANES != 0
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
        scratch_shapes=[pltpu.VMEM((stack * query_tile, LANES), jnp.float32),
                        pltpu.VMEM((stack * query_tile, LANES), jnp.float32),
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
    if all(width % LANES == 0 for width in widths):
        tiles = FUSED_QUERY_TILE, FUSED_KEY_TILE
    elif (all(2 * width == LANES for width in widths)
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

    Returns ``(out (T, H · dv), fused)``: the module's two paths, the
    blocked one in blocks of ``block`` queries, out of one
    ``lax.platform_dependent``, so ``fused`` says what was lowered."""
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
    :func:`causal_attention` returns it, the kernel where :func:`_fused_tiles`
    finds its tiles, either path with the same span, and ``scored`` () int32
    the (query, key) pairs of one head whose scores that path computes
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



def latent_attention(p, x, heads, eps, rotation: Rope, block):
    """Multi-head latent attention over x (T, hidden) float32, one window →
    ``((T, hidden) float32, fused)``, ``fused`` as :func:`causal_attention`
    returns it. Keys and values come through a latent of ``kv_rank`` (the
    weights' ``kv_norm``): ``[c ; k_r] = W_kv_down x``, ``c`` normed, each
    head's ``[k_nope ; v] = W_kv_up c``, the ``rope`` dimensions ``k_r`` (the
    rest of ``W_kv_down``'s columns) one key head for all; queries through a
    latent of their own where the weights hold ``"q_down"`` (normed, then
    ``W_q_up``), else straight from ``x`` (``"q"``), ``nope`` + ``rope`` a
    head, the widths read off the weights. ``rotation`` turns the rope
    dimensions of both (its ``theta``, by halves or ``interleave``d). Where
    the weights hold a ``"gate"`` (hidden × heads), each head's output is
    scaled by ``σ(x W_g)`` of its own, in float32, before ``W_o``. Each part
    of q, k and v is its own product with its columns of the projection, so
    it comes out in the layout the attention reads."""
    T = x.shape[0]
    act = p["out"].dtype
    kv_rank = p["kv_norm"].shape[0]
    rope = p["kv_down"].shape[1] - kv_rank
    width = p["q" if "q" in p else "q_up"].shape[1] // heads
    nope = width - rope
    # the scores' scale is linear through the query's projection and the
    # rotation: in the float32 input of that projection it costs the queries
    # no second rounding
    if "q" in p:
        query, q_w = x * width ** -0.5, p["q"]
    else:
        query = rms_norm(mixed_dot(x, p["q_down"]), p["q_norm"], eps
                         ) * width ** -0.5
        q_w = p["q_up"]
    down = mixed_dot(x, p["kv_down"])
    ckv = rms_norm(down[:, :kv_rank], p["kv_norm"], eps)
    k_rope = rotary(down[:, kv_rank:], rotation.theta,
                    interleave=rotation.interleave).astype(act)

    def part(latent, w, lo, hi):
        """The latent times columns lo:hi of every head's group of w."""
        columns = w.reshape(w.shape[0], heads, -1)[:, :, lo:hi]
        return mixed_dot(latent, columns.reshape(w.shape[0], -1), act)

    q_rope = rotary(part(query, q_w, nope, width).astype(jnp.float32),
                    rotation.theta, heads,
                    interleave=rotation.interleave).astype(act)
    q_rope = jnp.swapaxes(q_rope.reshape(T, heads, rope), 0, 1)
    v = p["kv_up"].shape[1] // heads - nope
    out, fused = causal_attention(
        part(query, q_w, 0, nope), q_rope,
        part(ckv, p["kv_up"], 0, nope), k_rope,
        part(ckv, p["kv_up"], nope, nope + v), block)
    if "gate" in p:
        gate = jax.nn.sigmoid(mixed_dot(x, p["gate"]))        # (T, heads)
        out = (out.reshape(T, heads, -1) * gate[..., None]).reshape(T, -1)
    return mixed_dot(out, p["out"]), fused
