"""The sequence scorer's head and counts, the same for every stack: the
final norm, ``pooled`` and ``logprobs`` (:func:`score_head`), with its
hand-written Pallas TPU kernel, :func:`fused_scoring_head`, which keeps the
logits on the chip, one path of two under one contract chosen where the
program is lowered (:func:`_head_blocks`), XLA's written logits the other;
and what the outputs gain beside them (:func:`program_counts`).
``jax.experimental.pallas`` is imported where the kernel is built.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.core import telemetry
from sparkdl_tpu.models.sequence_ops import LANES, MASKED, rms_norm

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
# memory, and the on-chip memory the kernel asks for.
HEAD_POSITION_BLOCKS = (1024, 512, 256)
HEAD_ROW_TILE = 512
HEAD_BLOCK_BYTES = 48 * 1024 * 1024
HEAD_VMEM_LIMIT = 100 * 1024 * 1024


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
        max_ref[...] = jnp.full(max_ref.shape, MASKED, jnp.float32)
        sum_ref[...] = jnp.zeros(sum_ref.shape, jnp.float32)
        hit_ref[...] = jnp.zeros(hit_ref.shape, jnp.float32)

    logits = lax.dot_general(x_ref[...], head_ref[...],
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    parts = [logits[:, at:at + LANES] for at in range(0, tile, LANES)]
    before = max_ref[...]
    highest = functools.reduce(jnp.maximum, parts, before)
    # the next id's place in this tile, were it here: lane + a part's first
    place = following_ref[...] - step * tile - lax.broadcasted_iota(
        jnp.int32, before.shape, 1)
    sum_ref[...] = sum_ref[...] * jnp.exp(before - highest) + sum(
        jnp.exp(part - highest) for part in parts)
    hit_ref[...] = hit_ref[...] + sum(
        jnp.where(place == at, part, 0.0)
        for at, part in zip(range(0, tile, LANES), parts))
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
        scratch_shapes=[pltpu.VMEM((block, LANES), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=HEAD_VMEM_LIMIT),
        name="fused_scoring_head", interpret=interpret,
    )(x, head, following.reshape(-1, 1))
    return out[:, 0]


def _head_blocks(window, hidden, rows, dtype):
    """What a lowering can see of whether the fused head applies, as the
    kernel's ``(block, tile)`` or None: a bfloat16 head of whole lanes of
    ``hidden``; the most positions a step that divide the window, and with
    them the most rows of the head a tile — whole lanes that divide the
    rows — whose blocks stay within ``HEAD_BLOCK_BYTES``."""
    if dtype != jnp.bfloat16 or hidden % LANES:
        return None
    for block in HEAD_POSITION_BLOCKS:
        if window % block:
            continue
        for tile in range(HEAD_ROW_TILE, 0, -LANES):
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
            telemetry.M_MOE_PRODUCT_VISITS: jnp.sum(
                stacked("product_visits"), 1),
            telemetry.M_MOE_PRODUCT_ROW_TILES: jnp.sum(
                stacked("product_row_tiles"), 1),
            telemetry.M_MOE_FUSED_PRODUCT_LAYERS: jnp.sum(
                stacked("fused_products"), 1),
            telemetry.M_MOE_FUSED_COMBINE_LAYERS: jnp.sum(
                stacked("fused_combine"), 1),
            telemetry.M_MOE_LOAD_MAX_OVER_MEAN: jnp.broadcast_to(
                jnp.stack([s["load_max_over_mean"] for s in stats]),
                (B, len(stats)))})
    return {**out, telemetry.PROGRAM_COUNTS: program}
