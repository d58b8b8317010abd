"""Linear attention with the gated delta rule — KDA, "Kimi Delta Attention",
the mixer of the ``bailing_hybrid`` family's linear layers — beside the
pre-norm stack's other mixers (``models/shortconv_moe.py``), and its
recurrence as a Pallas TPU kernel.

A layer that holds ``"kda"`` runs, over its normed input ``u``, with ``H``
heads of ``d`` (the weights' columns ÷ heads):

  ``q, k, v = silu(conv(W_{q,k,v} u))``, each a depthwise causal convolution
  of a few taps without a bias, zeros before the window
  (``sequence_ops.causal_taps``, the loop the short convolution runs);
  ``q ← q / ‖q‖ · d^−½``, ``k ← k / ‖k‖`` per head;
  ``β = σ(W_β u)``, one a head; ``g = lower · σ(e^{A_log} ⊙ (W_f u +
  dt_bias))`` per channel, in ``(lower, 0)``, ``A_log`` one a head;
  ``S_t = (I − β_t k_t k_tᵀ) Diag(e^{g_t}) S_{t−1} + β_t k_t v_tᵀ``, ``S_0 =
  0`` in each window, ``o_t = S_tᵀ q_t``;
  ``out = W_o [RMSNorm_head(o) ⊙ σ(W_g u)]``, one gate a head.

**The chunked form** (:func:`chunk_terms`): within a chunk of ``CHUNK``
positions the updates ``w_t`` the state receives solve one unit lower
triangular system, ``(I + A) W = Diag(β) (V − (K ⊙ e^Γ) S_0)``, with ``Γ``
the decays' running sum from the chunk's start and ``A_{ti} = β_t Σ_c
k_t[c] k_i[c] e^{Γ_t[c] − Γ_i[c]}`` (i < t); then ``O = (Q ⊙ e^Γ) S_0 +
M W`` with ``M`` the same sums of ``q`` (i ≤ t), and ``S_C = e^{Γ_C} ⊙ S_0
+ (K ⊙ e^{Γ_C − Γ})ᵀ W``. Everything but ``S_0`` is the chunk's own, so the
chunks' triangles, their inverses and the decayed operands are made for all
chunks at once, by XLA in float32 at full precision, and only the hand-over
of ``S`` is serial. A ratio ``e^{Γ_t − Γ_i}`` is made as ``e^{Γ_t − Γ_r} ·
e^{Γ_r − Γ_i}`` about a reference ``r`` at the start of ``t``'s sub-chunk of
``SUB_CHUNK`` positions: with ``g ≥ lower = −5`` no factor passes
``e^{5·15}``, which float32 holds, where across a chunk of 64 it would not.

**The serial part** (:func:`delta_rule`) has two paths under one contract,
chosen where the program is lowered (one ``lax.platform_dependent``, never at
run time): lowered for a TPU with bfloat16 weights and heads of whole lanes,
:func:`fused_delta_rule`, one Pallas TPU kernel whose grid walks each
(window, head)'s chunks in order with the ``d × d`` float32 state in on-chip
memory from the first chunk to the last — the products' operands bfloat16,
their accumulation float32; everywhere else a ``lax.scan`` over chunks in
float32, the kernel's oracle. Both return ``fused``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.models.sequence_ops import (LANES, causal_taps, mixed_dot,
                                             rms_norm)

CHUNK = 64
SUB_CHUNK = 16
# the most a factor's exponent may be: a sub-chunk's 15 steps of at most 5
# each are 75, and a ratio masked out later never overflows a sum of d terms
_MOST_EXPONENT = 80.0
# chunks a grid step of the kernel works one after the other: the first of
# these that divides a window's chunks
KERNEL_CHUNKS = (4, 2, 1)
_HIGHEST = lax.Precision.HIGHEST


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGHEST,
                      preferred_element_type=jnp.float32)


def chunk_terms(q, k, v, g, beta):
    """The chunked form's terms, for all chunks at once. ``q``, ``k``, ``v``,
    ``g`` (B, T, H · d) and ``beta`` (B, T, H) float32, ``q`` and ``k`` normed
    and ``q`` scaled; the window is padded with zeros to whole chunks (a
    position after the last touches none before it). Returns, chunks
    heads first — (B, H, n, C, …): ``qe = q ⊙ e^Γ``, ``ke = k ⊙ e^Γ``, ``v``,
    ``kl = k ⊙ e^{Γ_C − Γ}`` (C, d) each, ``solve = (I + A)^{-1} Diag(β)`` and
    ``scores = M`` (C, C) each, and ``decay = e^{Γ_C}`` (B, H, n, d)."""
    B, T, _ = q.shape
    H = beta.shape[-1]
    pad = -T % CHUNK
    n = (T + pad) // CHUNK

    def heads(a):
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        return jnp.transpose(a.reshape(B, n, CHUNK, H, -1), (0, 3, 1, 2, 4))

    q, k, v, g, beta = map(heads, (q, k, v, g, beta))
    beta = beta[..., 0]                                     # (B, H, n, C)
    gamma = jnp.cumsum(g, 3)                                # ≤ 0, falling
    subs = CHUNK // SUB_CHUNK
    # the reference of each sub-chunk: the running sum at its first position
    ref = gamma[:, :, :, ::SUB_CHUNK]                       # (B, H, n, S, d)
    near = jnp.exp(gamma.reshape(gamma.shape[:3] + (subs, SUB_CHUNK, -1))
                   - ref[..., None, :])                     # e^{Γ_t − Γ_r} ≤ 1

    def rows(a):                    # (…, C, d) → (…, S, C/S, d), decayed
        return a.reshape(near.shape) * near

    def ratios(a):
        """Σ_c a_t[c] k_i[c] e^{Γ_t[c] − Γ_i[c]} for every t, i of a chunk,
        a sub-chunk of rows t at a time, each about its own reference."""
        parts = [_dot("bhnrd,bhnid->bhnri", rows(a)[:, :, :, s], k * jnp.exp(
            jnp.minimum(ref[:, :, :, s, None] - gamma, _MOST_EXPONENT)))
            for s in range(subs)]
        return jnp.concatenate(parts, 3)                    # (B, H, n, C, C)

    t = jnp.arange(CHUNK)
    below = t[:, None] > t[None, :]
    a = jnp.where(below, ratios(k), 0.0) * beta[..., None]
    eye = jnp.broadcast_to(jnp.eye(CHUNK, dtype=jnp.float32), a.shape)
    solve = lax.linalg.triangular_solve(a, eye, left_side=True, lower=True,
                                        unit_diagonal=True)
    last = gamma[:, :, :, -1:]                              # (B, H, n, 1, d)
    return {"qe": q * jnp.exp(gamma), "ke": k * jnp.exp(gamma), "v": v,
            "kl": k * jnp.exp(last - gamma),
            "solve": solve * beta[..., None, :],
            "scores": jnp.where(below | (t[:, None] == t[None, :]),
                                ratios(q), 0.0),
            "decay": jnp.exp(last[:, :, :, 0])}


def _chunk_step(s, qe, ke, v, kl, solve, scores, decay):
    """One chunk against the state ``s`` (…, d, d): ``(o, s')``."""
    w = _dot("...ij,...jd->...id", solve,
             v - _dot("...ik,...kd->...id", ke, s))
    o = _dot("...ik,...kd->...id", qe, s) + _dot("...ij,...jd->...id",
                                                 scores, w)
    return o, decay[..., None] * s + _dot("...ik,...id->...kd", kl, w)


def scan_chunks(terms):
    """The hand-over of the state from chunk to chunk as a ``lax.scan`` in
    float32 at full precision: (B, H, n, C, d) float32 outputs."""
    B, H, n, _, d = terms["v"].shape

    def step(s, chunk):
        return _chunk_step(s, *chunk)[::-1]

    order = ("qe", "ke", "v", "kl", "solve", "scores", "decay")
    _, out = lax.scan(step, jnp.zeros((B, H, d, d), jnp.float32),
                      tuple(jnp.moveaxis(terms[name], 2, 0)
                            for name in order))
    return jnp.moveaxis(out, 0, 2)


def _delta_kernel(qe_ref, ke_ref, v_ref, klt_ref, solve_ref, scores_ref,
                  decay_ref, out_ref, state_ref, *, chunks):
    """A grid step: ``chunks`` chunks of one (window, head), in order, against
    the state in ``state_ref``, which the first chunk of a window zeroes."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    def dot(a, b):
        return jnp.dot(a, b.astype(a.dtype),
                       preferred_element_type=jnp.float32)

    for i in range(chunks):
        s = state_ref[...]
        w = dot(solve_ref[i], v_ref[i].astype(jnp.float32) - dot(ke_ref[i], s))
        out_ref[i] = (dot(qe_ref[i], s) + dot(scores_ref[i], w)).astype(
            out_ref.dtype)
        state_ref[...] = decay_ref[i] * s + dot(klt_ref[i], w)


def fused_delta_rule(terms, *, interpret=False):
    """:func:`scan_chunks`' contract as one Pallas TPU kernel: a grid of
    (window, head, step), the steps — ``chunks`` chunks each, the first of
    ``KERNEL_CHUNKS`` that divides the window's — in order, the ``d × d``
    float32 state in on-chip memory across them. Per chunk four products of
    bfloat16 operands with float32 accumulation: ``W = solve · (V − ke ·
    S)``, ``O = qe · S + scores · W``, ``S ← decay ⊙ S + klᵀ · W``; ``klᵀ``
    comes transposed from XLA, ``decay`` as a column. Returns (B, H, n, C, d)
    bfloat16."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, H, n, C, d = terms["v"].shape
    chunks = next(c for c in KERNEL_CHUNKS if n % c == 0)
    half = {name: terms[name].astype(jnp.bfloat16)
            for name in ("qe", "ke", "v", "solve", "scores")}
    klt = jnp.swapaxes(terms["kl"], 3, 4).astype(jnp.bfloat16)
    decay = terms["decay"][..., None]                       # (B, H, n, d, 1)

    def block(*tail):
        return pl.BlockSpec((None, None, chunks) + tail,
                            lambda b, h, i: (b, h, i) + (0,) * len(tail))

    return pl.pallas_call(
        functools.partial(_delta_kernel, chunks=chunks),
        grid=(B, H, n // chunks),
        in_specs=[block(C, d), block(C, d), block(C, d), block(d, C),
                  block(C, C), block(C, C), block(d, 1)],
        out_specs=block(C, d),
        out_shape=jax.ShapeDtypeStruct((B, H, n, C, d), jnp.bfloat16),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="fused_delta_rule", interpret=interpret,
    )(half["qe"], half["ke"], half["v"], klt, half["solve"], half["scores"],
      decay)


def delta_rule(q, k, v, g, beta, act):
    """The gated delta rule over windows: ``q``, ``k``, ``v``, ``g`` (B, T,
    H · d) and ``beta`` (B, T, H) float32 as the module's docstring makes
    them. Returns ``(o (B, T, H · d) float32, fused)``: the chunked form,
    its serial part :func:`fused_delta_rule` where the program is lowered
    for a TPU, the products' dtype ``act`` is bfloat16 and ``d`` whole lanes,
    else :func:`scan_chunks`, out of one ``lax.platform_dependent``."""
    B, T, _ = q.shape
    terms = chunk_terms(q, k, v, g, beta)

    def flat(o):                    # (B, H, n, C, d) → (B, T, H · d)
        o = jnp.transpose(o, (0, 2, 3, 1, 4)).astype(jnp.float32)
        return o.reshape(B, -1, o.shape[3] * o.shape[4])[:, :T]

    def plain(terms):
        return flat(scan_chunks(terms)), jnp.int32(0)

    def fused(terms):
        return flat(fused_delta_rule(terms)), jnp.int32(1)

    if act != jnp.bfloat16 or terms["v"].shape[-1] % LANES:
        return plain(terms)
    return lax.platform_dependent(terms, tpu=fused, default=plain)


def _l2_normed(a, heads):
    a = a.reshape(a.shape[:-1] + (heads, -1))
    return (a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)).reshape(
        a.shape[:-2] + (-1,))


def kda(p, u, eps, lower):
    """The KDA mixer over windows u (B, T, hidden) float32 → ``((B, T, hidden)
    float32, fused)``, ``fused`` as :func:`delta_rule` returns it; ``lower``
    the decays' floor (``kda_lower_bound``). Products in the weights' dtype
    with float32 accumulation; taps, norms, gates, decays and the state
    float32."""
    act = p["out"].dtype
    heads = p["beta"].shape[1]
    qkv = [jax.nn.silu(causal_taps(mixed_dot(u, p[name]), p[name + "_taps"]))
           for name in ("q", "k", "v")]
    d = qkv[0].shape[-1] // heads
    q = _l2_normed(qkv[0], heads) * d ** -0.5
    k = _l2_normed(qkv[1], heads)
    beta = jax.nn.sigmoid(mixed_dot(u, p["beta"]))
    rate = jnp.repeat(jnp.exp(p["A_log"].astype(jnp.float32)), d)
    g = lower * jax.nn.sigmoid(rate * (mixed_dot(u, p["f"])
                                       + p["dt_bias"].astype(jnp.float32)))
    o, fused = delta_rule(q, k, qkv[2], g, beta, act)
    B, T, _ = o.shape
    o = rms_norm(o.reshape(B, T, heads, d), p["o_norm"], eps) * \
        jax.nn.sigmoid(mixed_dot(u, p["gate"]))[..., None]
    return mixed_dot(o.reshape(B, T, -1), p["out"]), fused
