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
plain path makes the chunks' triangles, their inverses and the decayed
operands for all chunks at once, in float32 at full precision, and only the
hand-over of ``S`` is serial. A ratio ``e^{Γ_t − Γ_i}`` is made as ``e^{Γ_t
− Γ_r} · e^{Γ_r − Γ_i}`` about a reference ``r`` at the start of ``t``'s
sub-chunk of ``SUB_CHUNK`` positions: with ``g ≥ lower = −5`` no factor
passes ``e^{5·15}``, which float32 holds, where across a chunk of 64 it
would not.

**Two paths under one contract** (:func:`delta_rule`), chosen where the
program is lowered (one ``lax.platform_dependent``, never at run time):
lowered for a TPU with bfloat16 weights and heads of whole lanes,
:func:`fused_delta_rule`, one Pallas TPU kernel that takes ``q``, ``k``,
``v``, ``g`` and ``β`` themselves — each head's rows read where they lie in
(B, T, H · d), the output written there — and whose grid walks each
(window, head)'s chunks in order: a chunk's terms are made from its own rows
in on-chip memory, in float32 at full precision (:func:`_terms_of_chunks`:
``Γ`` as a product with a triangle of ones, the triangle's inverse by
substitution in blocks, :func:`_lower_inverse`, no ``triangular_solve``),
next to the ``d × d`` float32 state, which stays there from the first chunk
to the last — the state products' operands bfloat16, their accumulation
float32; everywhere else :func:`chunk_terms` and a ``lax.scan`` over chunks
in float32 (:func:`scan_chunks`), the kernel's oracle. Both return
``fused``.
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
# chunks a grid step of the kernel works one after the other, and heads
# whose hand-overs it interleaves: the first of each that divides a window's
# chunks, its heads
KERNEL_CHUNKS = (4, 2, 1)
KERNEL_HEADS = (2, 1)
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


def _lower_inverse(a):
    """``(I + a)⁻¹`` of each strictly lower triangular (C, C) float32 matrix
    of ``a`` (n, C, C), at full precision, by substitution in blocks. First
    the diagonal blocks of ``SUB_CHUNK``: a block of 2 is inverted by ``I −
    a`` itself, and each doubling joins two neighbours' inverses ``X₁``,
    ``X₂`` with ``−X₂ a₂₁ X₁`` under them, two products; the blocks are held
    packed, each block's rows on its own lanes (n, SUB_CHUNK, C), so that a
    product pushes one block's rows and not the chunk's. Then the block rows
    below the first, in order: ``X_j = D_j⁻¹ (E_j − a_{j,<j} X_{<j})``, two
    products a block row."""
    n, C, _ = a.shape
    row = lax.broadcasted_iota(jnp.int32, a.shape, 1)
    col = lax.broadcasted_iota(jnp.int32, a.shape, 2)
    same = row // SUB_CHUNK == col // SUB_CHUNK

    def mm(x, y):
        return jnp.matmul(x, y, precision=_HIGHEST)

    x = jnp.where(row == col, 1.0, 0.0) - jnp.where(row // 2 == col // 2, a,
                                                    0.0)
    x = sum(x[:, lo:lo + SUB_CHUNK] for lo in range(0, C, SUB_CHUNK))
    size = 2
    while size < SUB_CHUNK:
        joint = jnp.where((row // (2 * size) == col // (2 * size))
                          & (row // size != col // size), a, 0.0)
        x = x - mm(mm(x, joint), jnp.where(same, jnp.concatenate(
            [x] * (C // SUB_CHUNK), 1), 0.0))
        size *= 2
    done = [jnp.where(same[:, :SUB_CHUNK], x, 0.0)]
    for lo in range(SUB_CHUNK, C, SUB_CHUNK):
        rows = slice(lo, lo + SUB_CHUNK)
        below = mm(a[:, rows, :lo], jnp.concatenate(done, 1))
        done.append(jnp.where(same[:, rows], x, 0.0)
                    - mm(x[:, :, rows], below))
    return jnp.concatenate(done, 1)


def _terms_of_chunks(q, k, g, beta):
    """:func:`chunk_terms` of ``n`` chunks, each from its own ``q``, ``k``,
    ``g`` (n, C, d) and ``beta`` (n, 1, C) float32, at full precision, the
    chunks' products side by side: ``Γ`` as a product with the lower
    triangle of ones, ``A`` and ``M`` a sub-chunk of rows at a time,
    ``(I + A)⁻¹`` by :func:`_lower_inverse`. Returns ``qe``, ``ke``, ``kl``
    (n, C, d), ``solve``, ``scores`` (n, C, C) and ``decay`` (n, d, 1)."""
    n, C, d = g.shape

    def eye_and_lower(size):
        row = lax.broadcasted_iota(jnp.int32, (n, size, size), 1)
        col = lax.broadcasted_iota(jnp.int32, (n, size, size), 2)
        return row == col, row > col

    eye, below = eye_and_lower(C)
    gamma = jnp.matmul(jnp.where(below | eye, 1.0, 0.0), g,
                       precision=_HIGHEST)
    a_rows, m_rows = [], []
    for lo in range(0, C, SUB_CHUNK):
        ref = gamma[:, lo:lo + 1]
        near = jnp.exp(gamma[:, lo:lo + SUB_CHUNK] - ref)
        far = k * jnp.exp(jnp.minimum(ref - gamma, _MOST_EXPONENT))
        both = jnp.concatenate([k[:, lo:lo + SUB_CHUNK] * near,
                                q[:, lo:lo + SUB_CHUNK] * near], 1)
        sums = lax.dot_general(both, far, (((2,), (2,)), ((0,), (0,))),
                               precision=_HIGHEST,
                               preferred_element_type=jnp.float32)
        a_rows.append(sums[:, :SUB_CHUNK])
        m_rows.append(sums[:, SUB_CHUNK:])

    def column(row, eye):           # (n, 1, m) → (n, m, 1)
        return jnp.sum(jnp.where(eye, row, 0.0), axis=2, keepdims=True)

    a = jnp.where(below, jnp.concatenate(a_rows, 1), 0.0) * column(beta, eye)
    last = gamma[:, C - 1:]
    return {"qe": q * jnp.exp(gamma), "ke": k * jnp.exp(gamma),
            "kl": k * jnp.exp(last - gamma),
            "solve": _lower_inverse(a) * beta,
            "scores": jnp.where(below | eye, jnp.concatenate(m_rows, 1), 0.0),
            "decay": column(jnp.exp(last), eye_and_lower(d)[0])}


def _delta_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, out_ref, state_ref, *,
                  chunks):
    """A grid step: ``chunks`` chunks of each of a few heads of one window —
    first all their terms, side by side, from their own rows, which need no
    state; then the chunks in order, the heads' hand-overs interleaved,
    against the states in ``state_ref``, which the first chunk of a window
    zeroes."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    def dot(a, b, dims=((1,), (0,))):
        return lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                               (dims, ((), ())),
                               preferred_element_type=jnp.float32)

    heads, d, _ = state_ref.shape
    lanes = [slice(h * d, (h + 1) * d) for h in range(heads)]

    def by_chunk(ref):              # (chunks · C, heads · d) → (…, C, d)
        return jnp.concatenate([ref[:, h].reshape(chunks, CHUNK, d)
                                for h in lanes], 0)

    t = _terms_of_chunks(by_chunk(q_ref), by_chunk(k_ref), by_chunk(g_ref),
                         beta_ref[...].reshape(heads * chunks, 1, CHUNK))
    s = [state_ref[h] for h in range(heads)]
    for i in range(chunks):
        rows = slice(i * CHUNK, (i + 1) * CHUNK)
        for h, cols in enumerate(lanes):
            j = h * chunks + i
            w = dot(t["solve"][j], v_ref[rows, cols].astype(jnp.float32)
                    - dot(t["ke"][j], s[h]))
            out_ref[rows, cols] = (dot(t["qe"][j], s[h])
                                   + dot(t["scores"][j], w)).astype(
                out_ref.dtype)
            s[h] = t["decay"][j] * s[h] + dot(t["kl"][j], w, ((0,), (0,)))
    for h in range(heads):
        state_ref[h] = s[h]


def fused_delta_rule(q, k, v, g, beta, *, interpret=False):
    """:func:`delta_rule`'s contract as one Pallas TPU kernel, from its own
    inputs: a grid of (window, heads, step) — ``heads`` heads a step, the
    first of ``KERNEL_HEADS`` that divides H, and ``chunks`` chunks, the
    first of ``KERNEL_CHUNKS`` that divides the window's —, the steps in
    order, each head's ``d × d`` float32 state in on-chip memory across
    them. A step reads its rows of ``q``, ``k``, ``g`` (float32) and ``v``
    (bfloat16) where they lie in (B, T, H · d), a head a block of lanes,
    and ``β`` a chunk a row; it makes the chunks' terms in on-chip memory,
    in float32 at full precision (:func:`_terms_of_chunks`), then four
    products of bfloat16 operands with float32 accumulation a chunk: ``W =
    solve · (V − ke · S)``, ``O = qe · S + scores · W``, ``S ← decay ⊙ S +
    klᵀ · W``. Returns ``o`` (B, T, H · d) bfloat16, written where it
    lies."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, T, _ = q.shape
    H = beta.shape[-1]
    d = q.shape[-1] // H
    pad = -T % CHUNK
    n = (T + pad) // CHUNK
    chunks = next(c for c in KERNEL_CHUNKS if n % c == 0)
    heads = next(h for h in KERNEL_HEADS if H % h == 0)

    def padded(a):
        return jnp.pad(a, ((0, 0), (0, pad), (0, 0)))

    beta = jnp.swapaxes(padded(beta), 1, 2).reshape(B, H, n // chunks,
                                                    chunks, CHUNK)
    lanes = pl.BlockSpec((None, chunks * CHUNK, heads * d),
                         lambda b, h, i: (b, i, h))
    o = pl.pallas_call(
        functools.partial(_delta_kernel, chunks=chunks),
        grid=(B, H // heads, n // chunks),
        in_specs=[lanes] * 4 + [pl.BlockSpec(
            (None, heads, None, chunks, CHUNK),
            lambda b, h, i: (b, h, i, 0, 0))],
        out_specs=lanes,
        out_shape=jax.ShapeDtypeStruct((B, n * CHUNK, H * d), jnp.bfloat16),
        scratch_shapes=[pltpu.VMEM((heads, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="fused_delta_rule", interpret=interpret,
    )(padded(q), padded(k), padded(v.astype(jnp.bfloat16)), padded(g), beta)
    return o[:, :T]


def delta_rule(q, k, v, g, beta, act):
    """The gated delta rule over windows: ``q``, ``k``, ``v``, ``g`` (B, T,
    H · d) and ``beta`` (B, T, H) float32 as the module's docstring makes
    them. Returns ``(o (B, T, H · d) float32, fused)``: :func:`fused_delta_rule`
    where the program is lowered for a TPU, the products' dtype ``act`` is
    bfloat16 and ``d`` whole lanes, else the chunked form — :func:`scan_chunks`
    over :func:`chunk_terms` —, out of one ``lax.platform_dependent``."""
    B, T, _ = q.shape

    def plain(*args):
        o = jnp.transpose(scan_chunks(chunk_terms(*args)), (0, 2, 3, 1, 4))
        return o.reshape(B, -1, o.shape[3] * o.shape[4])[:, :T], jnp.int32(0)

    def fused(*args):
        return fused_delta_rule(*args).astype(jnp.float32), jnp.int32(1)

    if act != jnp.bfloat16 or q.shape[-1] // beta.shape[-1] % LANES:
        return plain(q, k, v, g, beta)
    return lax.platform_dependent(q, k, v, g, beta, tpu=fused, default=plain)


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
