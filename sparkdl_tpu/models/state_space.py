"""The state-space mixer of the ``jamba`` family (Mamba-1 with Jamba's inner
norms) — the third mixer of the pre-norm stack of ``models/shortconv_moe.py``,
beside the gated short convolution and grouped-query attention — and its
recurrence, the **selective scan**, as a Pallas TPU kernel.

A layer that holds ``"ssm"`` runs, over its normed input ``u``:

  ``[x ; z] = W_in u`` (``d_inner`` each, no bias);
  ``x ← silu(conv(x))``, a depthwise causal convolution of ``d_conv`` taps with
  a bias, zeros before the window (``sequence_ops.causal_taps``, the loop the
  gated short convolution runs);
  ``[δ ; B ; C] = W_x x`` (``dt_rank`` + ``d_state`` + ``d_state``), each
  through an RMSNorm with a gain of its own;
  ``Δ = softplus(W_dt δ + b_dt)`` (``d_inner`` wide), ``A = −exp(A_log)``;
  ``s_t = exp(Δ_t ⊙ A) ⊙ s_{t−1} + (Δ_t ⊙ x_t) ⊗ B_t``,
  ``y_t = s_t C_t + D ⊙ x_t``; ``out = W_out (y ⊙ silu(z))``.

Precision follows the weights, as in the rest of the stack: the five products
run in the weights' dtype with float32 accumulation; the taps, their bias and
silu, the inner norms' statistics, softplus and ``b_dt``, ``Δ``,
``exp(Δ ⊙ A)``, the state, ``D ⊙ x`` and the gate are float32. ``x`` and
``z`` are rounded once to the weights' dtype where they leave their
producers — ``x`` is an operand of ``W_x`` in that dtype anyway, and the
gated ``y`` one of ``W_out`` — so both cross HBM at two bytes a value;
softplus and ``b_dt`` are applied before the scan, by XLA, fused into the
product that makes ``Δ``.

**The recurrence is serial along the window and no product**: ``A`` is
diagonal by channel *and* state, so a position's update is ``d_inner ×
d_state`` independent multiply-adds on the vector unit. :func:`selective_scan`
has two paths under one contract, chosen where the program is lowered (one
``lax.platform_dependent``, never at run time): lowered for a TPU with
bfloat16 operands, a window of whole time blocks and a ``d_inner`` of whole
channel chunks it is :func:`fused_selective_scan`, which keeps the state on the chip
for the whole window — only ``x``, ``Δ``, ``B``, ``C``, ``z`` in and the
gated ``y`` out cross HBM, each once; everywhere else :func:`scan_blocks`,
a ``lax.scan`` over blocks of positions that never holds more than a block's
coefficients, which is also the oracle the kernel is tested against. Both
take ``s_0`` and return the last state: nothing else would have to outlive a
launch for a window to be scanned in pieces (the convolution's ``d_conv − 1``
positions before a piece are the other carried thing; here they are zeros).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.models.sequence_ops import (LANES, causal_taps, mixed_dot,
                                             rms_norm)

# The kernel's blocks: positions a grid step — a step's ``Δ`` (float32), ``x``,
# ``z`` and ``y`` blocks, each twice for the pipeline, and its float32
# ``Δ ⊙ x`` and ``y`` scratch are 20 MB at 128 positions × 5,120 channels —
# the channels whose state a loop over the block's positions carries in
# registers (16 states × 512 channels are 8 of the 64 vector registers, as
# many again hold their ``A``), and the positions one turn of that loop steps
# through. Chosen from chip runs at 16,384 positions × 5,120 channels × 16
# states (PERF.md §6, PR 44; ms a call by block × chunk × unroll): 128 × 512
# × 8 5.19, × 4 5.51, × 1 8.18; 128 × 1,024 × 4 5.27, 128 × 256 × 4 6.70;
# 256 × 512 × 4 5.52, 64 × 512 × 4 5.48; a chunk of one lane group, 128, is
# refused by the compiler (a dynamic row load it cannot align). Positions the
# plain path's block holds the coefficients of.
SCAN_TIME_BLOCK = 128
SCAN_CHANNEL_CHUNKS = (512, 256)
SCAN_UNROLL = 8
SCAN_VMEM_LIMIT = 64 * 1024 * 1024
PLAIN_BLOCK = 64


def scan_blocks(x, delta, a, b, c, d, z, state, block=PLAIN_BLOCK):
    """:func:`selective_scan`'s contract in XLA: a ``lax.scan`` over blocks of
    ``block`` positions (the most that divide the window). A block's
    ``exp(Δ ⊙ A)`` and ``(Δ ⊙ x) ⊗ B`` are made at once — they are the
    largest temporaries, ``block × d_state × d_inner`` float32 each — and its
    positions are stepped through one after the other, in the kernel's
    order."""
    T, D = x.shape
    block = math.gcd(T, block)
    f32, x_dtype = jnp.float32, x.dtype

    def blocks(v):
        return v.reshape((T // block, block) + v.shape[1:])

    def one_block(s, operands):
        x, delta, b, c, z = operands
        x = x.astype(f32)
        decay = jnp.exp(delta[:, None, :] * a)              # (block, N, D)
        driven = (delta * x)[:, None, :] * b[:, :, None]

        def one_position(s, step):
            decay, driven, c = step
            s = decay * s + driven
            return s, jnp.sum(s * c[:, None], 0)

        s, y = lax.scan(one_position, s, (decay, driven, c))
        z = z.astype(f32)
        return s, ((y + d * x) * (z * jax.nn.sigmoid(z))).astype(x_dtype)

    last, y = lax.scan(one_block, state, tuple(
        blocks(v) for v in (x, delta, b, c, z)))
    return y.reshape(T, D), last


def _scan_kernel(x_ref, delta_ref, z_ref, b_ref, c_ref, a_ref, d_ref, s0_ref,
                 y_ref, state_ref, driven_ref, acc_ref, b_wide, c_wide, *,
                 block, chunk, unroll):
    """One block of positions of every channel. ``state_ref`` — the output
    that returns the last state — stays on the chip from the first grid step
    to the last and is the recurrence's carry between steps. States lie on
    sublanes and channels on lanes: a position's ``B`` and ``C`` (``d_state``
    numbers on lanes as they arrive) are first turned onto sublanes and held
    across the lanes, once a step for all channels; then, ``chunk`` channels
    at a time, a loop over the block's positions carries their state in
    registers: ``Δ`` and ``Δ ⊙ x`` rows broadcast over the states,
    ``exp(Δ ⊙ A)``, the update, and ``y`` as the sum over the states'
    sublanes, one row a position. The epilogue ``(y + D ⊙ x) ⊙ silu(z)``
    runs on the whole block."""
    from jax.experimental import pallas as pl
    step = pl.program_id(0)
    states, channels = a_ref.shape
    f32 = jnp.float32

    @pl.when(step == 0)
    def _():
        state_ref[...] = s0_ref[...]

    x = x_ref[...].astype(f32)
    driven_ref[...] = delta_ref[...] * x
    diagonal = (lax.broadcasted_iota(jnp.int32, (states, states), 0)
                == lax.broadcasted_iota(jnp.int32, (states, states), 1))

    def widen(t, _):
        """Position t's B and C from a row (1, states) to (states, lanes)."""
        rows = pl.ds(pl.multiple_of(t * states, states), states)
        for narrow, wide in ((b_ref, b_wide), (c_ref, c_wide)):
            row = jnp.broadcast_to(narrow[pl.ds(t, 1), :], (states, states))
            column = jnp.sum(jnp.where(diagonal, row, 0.0), 1, keepdims=True)
            wide[rows, :] = jnp.broadcast_to(column, (states, LANES))

    lax.fori_loop(0, block, widen, None)

    def across(tile):
        return jnp.tile(tile, (1, chunk // LANES))

    for lo in range(0, channels, chunk):
        columns = slice(lo, lo + chunk)
        a = a_ref[:, columns]

        def positions(i, s, columns=columns, a=a):
            for t in range(unroll):     # (the loop's own unroll is all or 1)
                t = i * unroll + t
                rows = pl.ds(pl.multiple_of(t * states, states), states)
                decay = jnp.exp(delta_ref[pl.ds(t, 1), columns] * a)
                s = decay * s + driven_ref[pl.ds(t, 1), columns] * across(
                    b_wide[rows, :])
                acc_ref[pl.ds(t, 1), columns] = jnp.sum(
                    s * across(c_wide[rows, :]), 0, keepdims=True)
            return s

        state_ref[:, columns] = lax.fori_loop(
            0, block // unroll, positions, state_ref[:, columns])

    z = z_ref[...].astype(f32)
    y_ref[...] = ((acc_ref[...] + d_ref[...] * x)
                  * (z * jax.nn.sigmoid(z))).astype(y_ref.dtype)


def _scan_chunk(channels):
    """The most channels of ``SCAN_CHANNEL_CHUNKS`` that divide ``channels``,
    or None."""
    return next((chunk for chunk in SCAN_CHANNEL_CHUNKS
                 if channels % chunk == 0), None)


def fused_selective_scan(x, delta, a, b, c, d, z, state, *,
                         block=SCAN_TIME_BLOCK, chunk=None,
                         unroll=SCAN_UNROLL, interpret=False):
    """:func:`selective_scan`'s contract as one Pallas TPU kernel: the grid
    runs over blocks of ``block`` positions, one after the other, and the
    state of all ``d_inner × d_state`` values stays in on-chip memory from
    the first to the last (320 KB at 5,120 × 16). Only ``x``, ``Δ``, ``z``,
    ``B``, ``C`` in and the gated ``y`` out cross HBM, each once; ``A``,
    ``D`` and ``s_0`` are read once a call.

    The window is a multiple of ``block``, ``block`` of 8 (16 for bfloat16
    rows), and ``d_inner`` of ``chunk``, that of the 128 lanes."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    T, D = x.shape
    N = a.shape[0]
    chunk = chunk or _scan_chunk(D)

    def positions(width):
        return pl.BlockSpec((block, width), lambda i: (i, 0))

    def whole(rows):
        return pl.BlockSpec((rows, D), lambda i: (0, 0))

    y, last = pl.pallas_call(
        functools.partial(_scan_kernel, block=block, chunk=chunk,
                          unroll=unroll),
        grid=(T // block,),
        in_specs=[positions(D), positions(D), positions(D), positions(N),
                  positions(N), whole(N), whole(1), whole(N)],
        out_specs=[positions(D), whole(N)],
        out_shape=[jax.ShapeDtypeStruct((T, D), x.dtype),
                   jax.ShapeDtypeStruct((N, D), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, D), jnp.float32),
                        pltpu.VMEM((block, D), jnp.float32),
                        pltpu.VMEM((block * N, LANES), jnp.float32),
                        pltpu.VMEM((block * N, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=SCAN_VMEM_LIMIT),
        name="selective_scan", interpret=interpret,
    )(x, delta, z, b, c, a, d.reshape(1, D), state)
    return y, last


def selective_scan(x, delta, a, b, c, d, z, state):
    """One window's recurrence with its epilogue: ``x`` and ``z`` (T, d_inner)
    in the dtype the products run in, ``delta`` (T, d_inner), ``b`` and ``c``
    (T, d_state), ``a`` (d_state, d_inner) — ``−exp(A_log)``, states first —
    ``d`` (d_inner,) and ``state`` (d_state, d_inner) ``s_0``, all float32.
    ``s_t = exp(Δ_t ⊙ A) ⊙ s_{t−1} + (Δ_t ⊙ x_t) ⊗ B_t`` and
    ``y_t = (s_t C_t + D ⊙ x_t) ⊙ silu(z_t)``, in float32, ``y`` rounded once
    to ``x``'s dtype.

    Returns ``(y (T, d_inner), last (d_state, d_inner) float32, fused)``.
    Lowered for a TPU, with bfloat16 ``x`` and ``z``, a window of whole time
    blocks and a ``d_inner`` of whole channel chunks, this is
    :func:`fused_selective_scan` and ``fused`` is 1; everywhere else
    :func:`scan_blocks` and 0. Both come out of one
    ``lax.platform_dependent``, so ``fused`` says what was lowered."""
    def plain(*operands):
        return (*scan_blocks(*operands), jnp.int32(0))

    def fused(*operands):
        return (*fused_selective_scan(*operands), jnp.int32(1))

    operands = (x, delta, a, b, c, d, z, state)
    T, D = x.shape
    fits = (x.dtype == jnp.bfloat16 and z.dtype == jnp.bfloat16
            and T % SCAN_TIME_BLOCK == 0 and _scan_chunk(D) is not None)
    if not fits:
        return plain(*operands)
    return lax.platform_dependent(*operands, tpu=fused, default=plain)


def state_space(p, u, c, state=None):
    """The mixer over windows u (B, T, hidden) float32; ``c`` the stack's
    configuration (``d_inner``, ``d_state``, ``dt_rank``, ``eps``); ``state``
    (B, d_state, d_inner) float32 each window's ``s_0``, zeros where None.
    Returns ``(out (B, T, hidden) float32, last (B, d_state, d_inner), fused
    (B,) int32)``, the last two as :func:`selective_scan` returns them for
    each window."""
    B = u.shape[0]
    f32 = jnp.float32
    act = p["out"].dtype
    inner, states, rank = c.d_inner, c.d_state, c.dt_rank
    x = jax.nn.silu(causal_taps(mixed_dot(u, p["in"][:, :inner]), p["taps"],
                                p["conv_bias"])).astype(act)
    z = mixed_dot(u, p["in"][:, inner:], act)
    projected = mixed_dot(x, p["x"])
    dt, b, carried = (
        rms_norm(projected[..., lo:hi], p[name], c.eps)
        for name, lo, hi in (("dt_norm", 0, rank),
                             ("b_norm", rank, rank + states),
                             ("c_norm", rank + states, rank + 2 * states)))
    delta = jax.nn.softplus(mixed_dot(dt, p["dt"]) + p["dt_bias"].astype(f32))
    a = -jnp.exp(p["a_log"].astype(f32)).T
    d = p["d"].astype(f32)
    if state is None:
        state = jnp.zeros((B, states, inner), f32)

    def one_window(row):
        x, delta, b, carried, z, state = row
        return selective_scan(x, delta, a, b, carried, d, z, state)

    y, last, fused = lax.map(one_window, (x, delta, b, carried, z, state))
    return mixed_dot(y, p["out"]), last, fused
