"""The sparse expert layer of the sequence stacks, one config
(:class:`ExpertConfig`) whichever stack holds it: a router over every
published expert whose top-k is renormalised and scaled (:func:`route`),
and the part of the layer that the experts held here compute — every pair
that meets one, none dropped (:func:`routed_experts`) — with its two
hand-written Pallas TPU kernels, each one path of two under one contract,
chosen where the program is lowered, XLA's the other and the kernel's
oracle: the experts' grouped products (:func:`expert_products` →
:func:`grouped_product`) and the combine (:func:`combine` →
:func:`fused_combine`). ``jax.experimental.pallas`` is imported where a
kernel is built. :func:`routed_experts` returns the layer's part and one
record of what it did, which :func:`expert_stats` turns into a launch's
counts.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.models.sequence_ops import LANES


@dataclass(frozen=True)
class ExpertConfig:
    """The expert layer as published; ``experts_held`` is what this chip
    holds of a stated deployment."""

    expert_width: int
    experts: int              # published: the router's width
    experts_held: Tuple[int, ...]
    top_k: int
    scaling: float = 1.0
    norm_topk: bool = True
    topk_eps: float = 0.0     # added to the chosen scores' sum before dividing
    scoring: str = "sigmoid"  # the router's scores: "sigmoid" or "softmax"
    # group-limited routing: the experts in ``groups`` groups of equal size,
    # a token's top-k taken within the ``topk_group`` groups whose two best
    # scores sum highest (1, 1: over all experts)
    groups: int = 1
    topk_group: int = 1
    # rows of the grouped products' buffer, as a multiple of the pairs that
    # meet a held expert when routing is even (tokens·top_k·held/experts);
    # pairs beyond it take further rounds
    capacity_factor: float = 2.0


def route(p, x, c: ExpertConfig):
    """Float32 at full precision: (chosen ids (N, k), weights (N, k)). The
    scores are the logits' sigmoids, each expert by itself, or their soft-max
    over all published experts (``c.scoring``). Where the layer holds a
    selection bias (``"expert_bias"``), the top-k is taken of ``scores +
    bias`` and the weights are the unbiased scores of the chosen. With
    ``c.groups`` > 1 the routing is group-limited: a group's score is the
    sum of its two best of those (biased) scores, and the top-k is taken
    among the experts of the ``c.topk_group`` best groups only."""
    score = {"sigmoid": jax.nn.sigmoid, "softmax": jax.nn.softmax}[c.scoring]
    scores = score(jnp.dot(x.astype(jnp.float32),
                           p["router"].astype(jnp.float32),
                           precision=lax.Precision.HIGHEST))
    if "expert_bias" in p or c.groups > 1:
        choice = scores + p["expert_bias"].astype(jnp.float32) \
            if "expert_bias" in p else scores
        if c.groups > 1:
            grouped = choice.reshape(choice.shape[0], c.groups, -1)
            _, kept = lax.top_k(jnp.sum(lax.top_k(grouped, 2)[0], -1),
                                c.topk_group)
            kept = jnp.any(kept[..., None] == jnp.arange(c.groups), 1)
            choice = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(
                choice.shape)
        _, chosen = lax.top_k(choice, c.top_k)
        top = jnp.take_along_axis(scores, chosen, -1)
    else:
        top, chosen = lax.top_k(scores, c.top_k)
    if c.norm_topk:
        top = top / (jnp.sum(top, -1, keepdims=True) + c.topk_eps)
    return chosen, top * c.scaling


def buffer_capacity(tokens, c: ExpertConfig):
    """Rows of the grouped products' buffer for a launch of ``tokens``:
    ``capacity_factor`` × the pairs that meet a held expert when routing is
    even, and never more than the pairs there are — a layer held whole runs
    one round of exactly its pairs."""
    even = c.capacity_factor * tokens * c.top_k * len(c.experts_held) \
        / c.experts
    return min(max(8, -(-int(even) // 8) * 8), tokens * c.top_k)


# The grouped products' tiles, chosen from chip runs at 65,536 rows in 32
# groups, 2,048 × 1,792, and at 16,384 rows in 16 groups, 7,680 × 2,048
# (PERF.md §6). Rows a grid step, worked in sub-tiles of which a visit
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


def product_visits(sizes, rows, row_tile=GROUPED_ROW_TILE):
    """The visits of :func:`_group_visits`' grid over a buffer of ``rows``
    whose groups have ``sizes`` (traced), counted on either path: one for
    each (row tile, group) that share a row and one for each whole tile past
    the last group's end. A last tile of fewer rows counts as a tile."""
    tiles = -(-rows // row_tile)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    touched = jnp.where(sizes > 0,
                        (ends - 1) // row_tile - starts // row_tile + 1, 0)
    covered = -(-ends[-1] // row_tile)          # tiles that hold a group's row
    return jnp.sum(touched) + tiles - covered


def swiglu(gate, up, limit=0.0):
    """``silu(gate) · up`` of float32 products; with a ``limit`` (a layer's
    ``swiglu_limit``, 0: none) ``gate`` is first held under it and ``up``
    within ± it."""
    if limit:
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def _grouped_kernel(group_of, tile_of, offsets, rows_ref, *refs, row_tile,
                    sub_tile, limit):
    """One visit of :func:`_group_visits`: the tile's rows times the group's
    one weight block (``out = rows · w``) or two (``out = swiglu(rows ·
    gate, rows · up, limit)``, on the float32 accumulators), ``sub_tile``
    rows at a time in a loop — one product's code whatever the tile: only
    the sub-tiles that hold a row of the group are multiplied, and written to
    the rows that are the group's; the tile's other rows are left as the
    visit before wrote them, or zeroed on the tile's first visit."""
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
            acc = swiglu(*acc, limit) if len(acc) == 2 else acc[0]
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
    for parts in range(1, columns // LANES + 1):
        tile, rest = divmod(columns, parts)
        if rest or tile % LANES:
            continue
        blocks = 2 * (row_tile * contraction * 2
                      + operands * contraction * tile * 2
                      + row_tile * tile * out_bytes)
        if blocks <= GROUPED_BLOCK_BYTES:
            return tile
    return None


@functools.partial(jax.jit, static_argnames=(
    "out_dtype", "row_tile", "sub_tile", "limit", "interpret"))
def grouped_product(rows, weights, sizes, out_dtype, *,
                    row_tile=GROUPED_ROW_TILE, sub_tile=GROUPED_SUB_TILE,
                    limit=0.0, interpret=False):
    """The grouped product as one Pallas TPU kernel (the algorithm of jax's
    ``megablox.gmm``): ``rows`` (R, K) sorted by group, ``weights`` one array
    (groups, K, N) or two — ``rows · w[g]``, or ``swiglu(rows · gate[g],
    rows · up[g], limit)`` with both products and the epilogue on float32
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
                          sub_tile=min(sub_tile, row_tile), limit=limit),
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


def expert_products(rows, experts, sizes, limit=0.0):
    """The experts' three grouped products over a buffer sorted by expert:
    ``swiglu(rows · gate[g], rows · up[g], limit)``, rounded once to the
    weights' dtype, times ``down[g]``; ``rows`` (R, hidden) in that dtype,
    ``sizes`` (held,) int32 each expert's rows (it may be traced), float32
    accumulation and a float32 result, zero past the last expert's rows.

    Returns ``(out (R, hidden) float32, fused)``. Lowered for a TPU, with
    bfloat16 operands, a buffer of whole row tiles and widths of whole lanes,
    this is :func:`grouped_product` twice — gate and up in one pass over the
    rows, their float32 results never in HBM — and ``fused`` is 1; everywhere
    else three ``lax.ragged_dot`` and 0. Both come out of one
    ``lax.platform_dependent``, so ``fused`` says what was lowered."""
    def ragged(rows, gate, up, down, sizes):
        gated = lax.ragged_dot(rows, gate, sizes,
                               preferred_element_type=jnp.float32)
        if limit:
            gated = jnp.minimum(gated, limit)
        gated = jax.nn.silu(gated)
        up = lax.ragged_dot(rows, up, sizes,
                            preferred_element_type=jnp.float32)
        if limit:
            up = jnp.clip(up, -limit, limit)
        hidden = gated * up
        return lax.ragged_dot(hidden.astype(rows.dtype), down, sizes,
                              preferred_element_type=jnp.float32
                              ), jnp.int32(0)

    def kernels(rows, gate, up, down, sizes):
        hidden = grouped_product(rows, (gate, up), sizes, rows.dtype,
                                 limit=limit)
        return grouped_product(hidden, (down,), sizes, jnp.float32
                               ), jnp.int32(1)

    operands = (rows, experts["gate"], experts["up"], experts["down"], sizes)
    _, hidden, width = experts["gate"].shape
    fits = (all(a.dtype == jnp.bfloat16 for a in operands[:4])
            and rows.shape[0] % GROUPED_ROW_TILE == 0
            and hidden % LANES == 0 and width % LANES == 0
            and _column_tile(hidden, width, 2, GROUPED_ROW_TILE, 2)
            and _column_tile(width, hidden, 1, GROUPED_ROW_TILE, 4))
    if not fits:
        return ragged(*operands)
    return lax.platform_dependent(*operands, tpu=kernels, default=ragged)


# The combine kernel's tiles: tokens a grid step, the most of these that
# divide the tokens and keep a step's blocks — the fetched rows of two tiles
# and the tile of ``y`` in and out, each twice for the pipeline — within
# ``COMBINE_BLOCK_BYTES``. Chosen from chip runs of the kernel alone at the
# four expert cells' layers (PERF.md §6): 128 tokens took 2.48 /
# 4.58 / 4.65 ms where 256 took 2.64 / 4.75 / 4.84 (layers held whole), 64
# and 128 the same 2.21–2.23 where 16 of 256 are held; 256 of openPangu's
# 7,680 wide rows overflow the chip's 128 MiB.
COMBINE_TOKEN_TILES = (128, 64, 32, 16, 8)
COMBINE_BLOCK_BYTES = 48 * 1024 * 1024
# float32 rows the combine's gathers — XLA's path, everywhere the combine
# kernel is not taken (see combine) — may hold at once
COMBINE_BYTES = 128 * 1024 * 1024


def _combine_tile(tokens, hidden, top_k):
    """The most tokens a grid step of :func:`fused_combine` among
    ``COMBINE_TOKEN_TILES`` that divide ``tokens``, or None."""
    for tile in COMBINE_TOKEN_TILES:
        if tokens % tile == 0 and 4 * tile * hidden * (2 * top_k + 4) \
                <= COMBINE_BLOCK_BYTES:
            return tile
    return None


def _lane_rows(a):
    """``a`` (R, hidden), R a multiple of 8 and ``hidden`` of the 128 lanes,
    as the (R / 8, hidden / 128, 8, 1, 128) array its (8, 128) tiles already
    are in HBM — XLA lowers this to a bitcast. A row is then one slice
    ``[r // 8, :, r % 8]`` of whole (1, 128) tiles, which a DMA may copy,
    where a row of the (8, 128)-tiled (R, hidden) is a slice across tiles
    that the kernel's compiler refuses."""
    rows, hidden = a.shape
    return a.reshape(rows // 8, 8, hidden // LANES, LANES).transpose(
        0, 2, 1, 3).reshape(rows // 8, hidden // LANES, 8, 1, LANES)


def _lane_block(lanes):
    """Lane groups of a token's row summed together between two skips: the
    most, up to 16, that divide ``lanes``."""
    return max(b for b in range(1, 17) if lanes % b == 0)


def _combine_bits(tile, top_k):
    """Bits of a pair's place in its tile of :func:`fused_combine`: its
    token's (``tile`` a power of two), then, above them, its k's."""
    t_bits = tile.bit_length() - 1
    return t_bits, t_bits + (top_k - 1).bit_length()


def _combine_kernel(pairs, starts, weights_ref, out_ref, y_ref, y_out_ref,
                    fetched, copies, held, *, top_k, tile):
    """One tile of tokens: starts the copies of the next tile's rows, waits
    for this tile's (started a step before), and adds ``Σ_k w · row`` in k
    order, in float32, to the tile of ``y``, 8 tokens and a block of lane
    groups at a time. ``pairs[starts[i]:starts[i + 1]]`` are tile i's pairs
    that fetch a row, each its row and its place in the tile in the bits
    :func:`_combine_bits` gives; a k at which none of 8 tokens fetched a row
    is skipped: ``held[slot]`` notes, per 8 tokens and k, whether one did."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    step = pl.program_id(0)
    groups = tile // 8
    places = tile * top_k
    t_bits, place_bits = _combine_bits(tile, top_k)

    def start(index, slot):
        def clear(i, _):
            held[slot, i] = 0

        def pair(j, _):
            packed = pairs[j]
            row = lax.shift_right_logical(packed, place_bits)
            k = lax.shift_right_logical(packed, t_bits) & (
                (1 << (place_bits - t_bits)) - 1)
            t = packed & (tile - 1)
            group = lax.shift_right_logical(t, 3)
            pltpu.make_async_copy(
                out_ref.at[lax.shift_right_logical(row, 3), :, row & 7],
                fetched.at[slot, k, group, :, t & 7], copies.at[slot]).start()
            held[slot, group * top_k + k] = 1

        lax.fori_loop(0, groups * top_k, clear, None)
        lax.fori_loop(starts[index], starts[index + 1], pair, None)

    @pl.when(step == 0)
    def _():
        start(0, 0)

    @pl.when(step + 1 < pl.num_programs(0))
    def _():
        start(step + 1, (step + 1) % 2)

    slot = step % 2
    # a DMA semaphore counts bytes: the tile's copies are waited for as
    # whole powers of two of rows, one wait for each bit of their number
    started = starts[step + 1] - starts[step]
    for bit in range(places.bit_length()):
        copied = 1 << bit
        if copied < 8:
            whole = fetched.at[slot, 0, 0, :, pl.ds(0, copied)]
        elif copied <= tile:
            whole = fetched.at[slot, 0, pl.ds(0, copied // 8)]
        else:
            whole = fetched.at[slot, pl.ds(0, copied // tile)]

        @pl.when((started >> bit) & 1 == 1)
        def _():
            pltpu.make_async_copy(whole, whole, copies.at[slot]).wait()

    # the same memory as (8, 128) tiles: a tile holds 8 tokens' 128 lanes
    rows = fetched.reshape(fetched.shape[:5] + (LANES,))
    lanes = rows.shape[3]
    block = _lane_block(lanes)

    def eight_tokens(a, _):
        tokens = pl.ds(pl.multiple_of(a * 8, 8), 8)
        weights = [jnp.broadcast_to(weights_ref[a, :, k:k + 1], (8, LANES))
                   for k in range(top_k)]
        for first in range(0, lanes, block):
            total = tuple(jnp.zeros((8, LANES), jnp.float32)
                          for _ in range(block))
            for k, w in enumerate(weights):
                def add(total, k=k, w=w):
                    return tuple(
                        part + jnp.where(w != 0, rows[slot, k, a, first + i],
                                         0.0) * w
                        for i, part in enumerate(total))

                total = lax.cond(held[slot, a * top_k + k] == 1, add,
                                 lambda total: total, total)
            for i, part in enumerate(total):
                at = pl.ds((first + i) * LANES, LANES)
                y_out_ref[tokens, at] = y_ref[tokens, at] + part

    lax.fori_loop(0, groups, eight_tokens, None)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def fused_combine(y, out, rows_of, weights, *, tile, interpret=False):
    """The expert layer's combine as one Pallas TPU kernel: ``y`` (N, hidden)
    float32 plus, for each token n, ``Σ_k weights[n, k] · out[rows_of[n ·
    top_k + k]]`` over its ``top_k`` pairs in k order, in float32. ``out``
    (rows, hidden) float32 stays in HBM, and only the rows of pairs whose
    ``rows_of`` is not −1 are copied to the chip, one DMA a row, a tile's
    copies started while the tile before is summed; a pair of −1 (its weight
    0) fetches nothing, and a token with none leaves its row of ``y`` as it
    was. ``y`` is updated in place. ``N`` is a multiple of ``tile``, that and
    ``rows`` of 8, and ``hidden`` of the 128 lanes.

    On the chip (PERF.md §6; 16,384 tokens): 2.21 ms against the gathers'
    12.19 where 16 of 256 experts are held (131,072 pairs of width 7,680 of
    which 8,205 fetch a row: the gathers fetch every row, 95 % of them to
    multiply by nought; the kernel's floor is ``y``'s 1.0 GB through HBM);
    where a layer is held whole (the tile's comment has those times) 4–11 %
    under the gathers, bound by issuing a copy a row, about 35 ns each, not
    by the bytes. Bit-equal to the gathers at all four."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    tokens, hidden = y.shape
    top_k = weights.shape[1]
    places = tile * top_k
    t_bits, place_bits = _combine_bits(tile, top_k)
    # the pairs that fetch a row, tile by tile, in (token, k) order, each
    # its row and its place in the tile in the bits of one int32
    fetch = rows_of >= 0
    before = jnp.cumsum(fetch, dtype=jnp.int32)
    place = jnp.arange(rows_of.shape[0], dtype=jnp.int32) % places
    pairs = jnp.zeros_like(rows_of).at[
        jnp.where(fetch, before - 1, rows_of.shape[0])].set(
            (rows_of << place_bits) | (place % top_k << t_bits)
            | place // top_k, mode="drop")
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              before.reshape(-1, places)[:, -1]])

    def tokens_of(i, pairs, starts):
        return i, 0

    return pl.pallas_call(
        functools.partial(_combine_kernel, top_k=top_k, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tokens // tile,),
            in_specs=[pl.BlockSpec((tile // 8, 8, top_k),
                                   lambda i, pairs, starts: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((tile, hidden), tokens_of)],
            out_specs=pl.BlockSpec((tile, hidden), tokens_of),
            scratch_shapes=[
                pltpu.VMEM((2, top_k) + _lane_rows(y[:tile]).shape,
                           jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((2, tile // 8 * top_k), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct(y.shape, jnp.float32),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=GROUPED_VMEM_LIMIT),
        name="fused_combine", interpret=interpret,
    )(pairs, starts, weights.reshape(tokens // 8, 8, top_k), _lane_rows(out),
      y)


def gathered_combine(y, out, at, weights, top_k):
    """``y`` (N, hidden) float32 plus each token's ``top_k`` rows of ``out``
    (rows, hidden) float32 weighed, as XLA's gathers: ``at`` (N·top_k,)
    int32 each pair's row, ``weights`` (N·top_k,) float32 each pair's weight
    — nought where the pair has no row in ``out``, whose ``at`` may then be
    anything (it is clipped into ``out``, and its row weighed by nought). A
    tile of tokens at a time, one gather for each of the ``top_k``, added in
    k order."""
    N, hidden = y.shape
    # the gathers of a tile of tokens are alive side by side: in tiles, so
    # that they stay under COMBINE_BYTES (whole, a launch of 16,384 tokens of
    # width 7,680 with 8 pairs each held 3.5 GiB of them)
    tiles = 1
    while (at.shape[0] * hidden * 4 > COMBINE_BYTES * tiles
           and N % (2 * tiles) == 0):
        tiles *= 2
    rows = jnp.clip(at, 0, out.shape[0] - 1).reshape(tiles, -1, top_k)
    weights = weights.reshape(rows.shape)

    def add_tile(i, y):
        first = i * (N // tiles)
        tile = lax.dynamic_slice_in_dim(y, first, N // tiles) + sum(
            out[rows[i, :, k]] * weights[i, :, k, None] for k in range(top_k))
        return lax.dynamic_update_slice_in_dim(y, tile, first, 0)

    return lax.fori_loop(0, tiles, add_tile, y)


def combine(y, out, at, weights, top_k):
    """:func:`gathered_combine`'s sum. Lowered for a TPU, where the buffer
    ``out`` has fewer rows than there are pairs — a layer that holds a share
    of the experts, whose absent pairs are rows the kernel does not fetch —
    with a ``hidden`` of whole lanes, tokens of a tile :func:`_combine_tile`
    finds and buffer rows of whole eights, it is :func:`fused_combine` — a
    pair of weight nought fetches no row — and ``fused`` is 1; everywhere
    else the gathers, and 0. Both come out of one ``lax.platform_dependent``,
    so ``fused`` says what was lowered. The same products added in the same
    order: equal on the chip to the bit, but for the sign of a zero.

    Where the buffer has a row for every pair (a layer held whole) both
    fetch every row, and the gathers stay: alone the kernel was 4–11 %
    quicker there, but in the program it left Mellum2's launch 5.6 ms
    slower (289.6 → 295.2), LFM2's 0.7 ms (281.5 → 282.2) and Laguna's
    0.7 ms quicker (212.0 → 211.2) (PERF.md §6), no gain to keep.

    Returns ``(y, fused)``."""
    N, hidden = y.shape

    def gathers(y, out, at, weights):
        return gathered_combine(y, out, at, weights, top_k), jnp.int32(0)

    def kernel(y, out, at, weights):
        rows_of = jnp.where(weights != 0, at, -1)
        return fused_combine(y, out, rows_of, weights.reshape(N, top_k),
                             tile=tile), jnp.int32(1)

    tile = _combine_tile(N, hidden, top_k)
    operands = (y, out, at, weights)
    if (out.shape[0] >= at.shape[0] or out.dtype != jnp.float32
            or hidden % LANES or out.shape[0] % 8 or tile is None
            or out.shape[0] << _combine_bits(tile, top_k)[1] >= 2 ** 31):
        return gathers(*operands)
    return lax.platform_dependent(*operands, tpu=kernel, default=gathers)


def routed_experts(p, x, c: ExpertConfig, limit=0.0):
    """The held experts' part of the layer for tokens x (N, hidden) float32;
    ``limit`` the layer's ``swiglu_limit`` (:func:`swiglu`).

    The (token, expert) pairs that meet a held expert are sorted by expert
    into one flat buffer of :func:`buffer_capacity` rows; a round gathers the
    buffer's tokens and runs the three grouped products
    (:func:`expert_products`, group sizes = each expert's pairs in the
    buffer). Pairs beyond the buffer take further rounds: none is dropped; a
    layer held whole has a row for every pair and runs one round without the
    loop.

    **The products are a kernel on a TPU.** On the chip (PERF.md §6; the
    three products alone) they take 8.3 ms where a layer of 32 experts is
    held whole (65,536 rows, 2,048 × 1,792; 173 TFLOP/s) against 19.6 ms as
    three ``lax.ragged_dot`` (73 TFLOP/s), and 6.8 ms where 16 of 256 are held
    (a buffer that routing fills by half: a tile without a pair is zeroed,
    not multiplied) against 10.3.

    **The combine is linear in the pairs.** The sort is a permutation, so its
    inverse says where each token's ``top_k`` results lie in the buffer; a
    round weighs each by its router weight — nought where the expert is
    absent or the pair lies in another round — and adds them to ``y`` in k
    order, in float32 (:func:`combine`, which has the numbers of its two
    paths). The gathers had replaced a one-hot product tokens × buffer on
    the MXU (24–26 ms held whole, 23.0 ms in part; a row scatter-add 47
    ms); their tiles bound what is alive at once (untiled, 3.5 GiB more in
    part).

    Returns ``(y (N, hidden) float32, told)``, ``told`` the record of the
    layer: ``chosen`` (N, k) the experts each token chose, ``counts`` (held,)
    the pairs per held expert, ``overflow`` (N·k,) bool per pair: computed
    beyond the first round, ``fused_products`` () int32 as
    :func:`expert_products` returns it and ``fused_combine`` () int32 as
    :func:`combine` returns it."""
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

    def one_round(r, carry):
        y = carry[0]
        lo = r * capacity
        pair = order[jnp.clip(lo + jnp.arange(capacity), 0, pairs - 1)]
        rows = xa[pair // c.top_k]                         # (capacity, hidden)
        sizes = (jnp.clip(ends - lo, 0, capacity)
                 - jnp.clip(starts - lo, 0, capacity)).astype(jnp.int32)
        out, fused = expert_products(rows, experts, sizes, limit)
        at = place - lo
        mine = jnp.where((at >= 0) & (at < capacity), held_weights, 0.0)
        return (*combine(y, out, at, mine, c.top_k), fused)

    none = jnp.zeros(x.shape, jnp.float32), jnp.int32(0), jnp.int32(0)
    if capacity == pairs:
        y, fused_combine, fused = one_round(0, none)
    else:       # rounds: 1 unless the held experts are full
        y, fused_combine, fused = lax.fori_loop(
            0, -(-local // capacity), one_round, none)
    overflow = jnp.zeros((pairs,), bool).at[order].set(
        (slots[order] < held) & (jnp.arange(pairs) >= capacity))
    return y, {"chosen": chosen, "counts": counts, "overflow": overflow,
               "fused_products": fused, "fused_combine": fused_combine}


def expert_stats(told, rows, c: ExpertConfig):
    """What an expert layer reports of a launch of ``rows`` windows, from
    :func:`routed_experts`' record ``told``: per row the tokens per published
    expert, the pairs that met a held expert, those computed beyond the first
    round, its share of the rows the grouped products ran (rounds × the
    buffer), of the grouped kernel's visits (:func:`product_visits`, each
    round's groups the held experts' pairs that fall in it) and of the
    buffer's row tiles, and whether those products and the combine were
    lowered to their kernels; of the launch, the fullest held expert's pairs
    over the mean."""
    chosen, counts = told["chosen"], told["counts"]
    held = jnp.asarray(c.experts_held, jnp.int32)
    per_row = jnp.sum(chosen.reshape(rows, -1, 1) == jnp.arange(c.experts), 1)
    capacity = buffer_capacity(chosen.shape[0], c)
    rounds = -(-jnp.sum(counts) // capacity)
    ran = (rounds * capacity).astype(jnp.int32)
    first = jnp.arange(-(-chosen.size // capacity))[:, None] * capacity
    ends = jnp.cumsum(counts)
    sizes = (jnp.clip(ends - first, 0, capacity)
             - jnp.clip(ends - counts - first, 0, capacity))
    visits = jnp.sum(jnp.where(
        jnp.arange(sizes.shape[0]) < rounds,
        jax.vmap(lambda s: product_visits(s, capacity))(sizes), 0))
    tiles = (rounds * -(-capacity // GROUPED_ROW_TILE)).astype(jnp.int32)

    def spread(total):
        """A whole number a row, and the rows' sum is the launch's."""
        return total // rows + (jnp.arange(rows) < total % rows)

    return {
        "expert_counts": per_row.astype(jnp.int32),              # (B, experts)
        "local_pairs": jnp.sum(per_row[:, held], -1).astype(jnp.int32),
        "overflow_pairs": jnp.sum(told["overflow"].reshape(rows, -1), -1
                                  ).astype(jnp.int32),
        "buffer_rows": spread(ran),
        "product_visits": spread(visits.astype(jnp.int32)),
        "product_row_tiles": spread(tiles),
        "fused_products": jnp.broadcast_to(told["fused_products"], (rows,)),
        "fused_combine": jnp.broadcast_to(told["fused_combine"], (rows,)),
        "load_max_over_mean": jnp.max(counts) / jnp.maximum(
            jnp.mean(counts.astype(jnp.float32)), 1.0),
    }
