"""Causal attention (models/attention.py) on the CPU: the blocked path over
grouped keys against the keys repeated; the fused kernel, interpreted, against
the blocked path and a float32 soft-max — at the latent model's head widths
with the rotary part, on grouped keys with a span or without, and at a head
width of 64 with a key head's query heads stacked —, that it is causal and
reads a span and no further, the key tiles it visits, and which of the two
paths a lowering takes."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.models import attention

WINDOW = 24


def distance(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


def test_blocked_attention_reads_grouped_keys_without_copies():
    """8 query heads on 2 key heads: the same as the keys repeated."""
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (h, WINDOW, 8))
               for i, h in enumerate((8, 2, 2)))
    with jax.default_matmul_precision("highest"):
        grouped = attention._blocked_attention(q, k, v, 7)
        repeated = attention._blocked_attention(
            q, jnp.repeat(k, 4, 0), jnp.repeat(v, 4, 0), 7)
    np.testing.assert_allclose(grouped, repeated, rtol=1e-5, atol=1e-6)


# -- the fused kernel on the latent model's operands ------------------------

HEADS, NOPE, ROPE, WIDTH = 4, 128, 64, 128       # the published head widths
KEY_HEADS = 2                                    # on grouped keys


def attention_operands(window, dtype=jnp.bfloat16, seed=0):
    """causal_attention's operands, the queries carrying their scale."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    scale = (NOPE + ROPE) ** -0.5

    def draw(key, *shape):
        return jax.random.normal(key, shape, jnp.float32)

    return tuple(a.astype(dtype) for a in (
        draw(keys[0], window, HEADS * NOPE) * scale,
        draw(keys[1], HEADS, window, ROPE) * scale,
        draw(keys[2], window, HEADS * NOPE), draw(keys[3], window, ROPE),
        draw(keys[4], window, HEADS * WIDTH)))


def exact_latent_attention(q_nope, q_rope, k_nope, k_rope, v):
    """A float32 soft-max at full precision over the same operands."""
    q_nope, q_rope, k_nope, k_rope, v = (
        a.astype(jnp.float32) for a in (q_nope, q_rope, k_nope, k_rope, v))
    window = v.shape[0]

    def heads(a):
        return a.reshape(window, HEADS, -1)

    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("qhd,khd->hqk", heads(q_nope), heads(k_nope)
                            ) + jnp.einsum("hqd,kd->hqk", q_rope, k_rope)
        scores = jnp.where(jnp.tril(jnp.ones((window, window), bool)),
                           scores, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1),
                          heads(v)).reshape(window, -1)


@pytest.mark.parametrize("window,query_tile", [(256, 128), (512, 128),
                                               (512, 256)])
def test_fused_kernel_against_the_blocked_path_and_float32(window,
                                                           query_tile):
    operands = attention_operands(window)
    fused = attention.fused_causal_attention(
        *operands, query_tile=query_tile, key_tile=128, interpret=True)
    blocked, engaged = attention.causal_attention(*operands, 128)
    assert engaged == 0 and fused.dtype == blocked.dtype == jnp.bfloat16
    exact = exact_latent_attention(*operands)
    to_exact = distance(fused, exact), distance(blocked, exact)
    # both are the bfloat16 rounding of the weights and of the output, about
    # 0.002 of the norm; neither path may be the looser by more than a quarter
    assert max(to_exact) < 0.003
    assert max(to_exact) < 1.25 * min(to_exact)
    assert distance(fused, blocked) < 0.004


@pytest.mark.parametrize("window", [256, 512])
def test_fused_kernel_is_causal(window):
    """A changed last key (both parts, and its value) moves the last query's
    row and no other."""
    q_nope, q_rope, k_nope, k_rope, v = attention_operands(window, seed=1)
    run = functools.partial(attention.fused_causal_attention,
                            query_tile=128, key_tile=128, interpret=True)
    before = np.asarray(run(q_nope, q_rope, k_nope, k_rope, v), np.float32)
    after = np.asarray(run(
        q_nope, q_rope, k_nope.at[-1].add(3.0), k_rope.at[-1].add(3.0),
        v.at[-1].add(3.0)), np.float32)
    np.testing.assert_array_equal(after[:-1], before[:-1])
    assert np.abs(after[-1] - before[-1]).max() > 0.1


@pytest.mark.parametrize("case", [
    "window-24", "float32", "lowered-for-cpu", "lowered-for-tpu"])
def test_the_choice_follows_what_the_lowering_can_see(case):
    """The kernel is taken where the program is lowered for a TPU with
    bfloat16 operands and a window of whole query tiles; the counter says
    which."""
    platform = "tpu" if case.endswith("lowered-for-tpu") else "cpu"
    window = 24 if case == "window-24" else attention.FUSED_QUERY_TILE
    dtype = jnp.float32 if case == "float32" else jnp.bfloat16
    operands = attention_operands(window, dtype)
    fn = jax.jit(lambda *a: attention.causal_attention(*a, 512))
    text = fn.trace(*operands).lower(lowering_platforms=(platform,)).as_text()
    assert text.count("tpu_custom_call") == (platform == "tpu")
    if platform == "tpu":
        return                       # nothing here can run it
    out, engaged = fn(*operands)
    assert engaged == 0
    assert out.shape == (window, HEADS * WIDTH)
    assert distance(out, exact_latent_attention(*operands)) < 0.003


# -- the fused kernel with a span, on grouped keys ----------------------------


def grouped_operands(window, dtype=jnp.bfloat16, seed=0):
    """grouped_causal_attention's operands: float32 queries (carrying the
    scale) and keys as a rotation leaves them, values in ``dtype``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (window, HEADS * WIDTH)) * WIDTH ** -0.5 * 3
    k = jax.random.normal(keys[1], (window, KEY_HEADS * WIDTH))
    v = jax.random.normal(keys[2], (window, KEY_HEADS * WIDTH)).astype(dtype)
    return q, k, v


def exact_grouped_attention(q, k, v, heads, span):
    """A float32 soft-max at full precision over the operands as the paths
    round them, keys repeated to the query heads, the band as a mask."""
    window, width = q.shape[0], q.shape[1] // heads
    q, k, v = (jnp.swapaxes(a.astype(jnp.bfloat16).astype(
        jnp.float32).reshape(window, -1, width), 0, 1) for a in (q, k, v))
    k, v = (jnp.repeat(a, heads // k.shape[0], 0) for a in (k, v))
    at = jnp.arange(window)
    seen = at[None, :] <= at[:, None]
    if span is not None:
        seen &= at[None, :] > at[:, None] - span
    with jax.default_matmul_precision("highest"):
        scores = jnp.where(seen, jnp.einsum("hqd,hkd->hqk", q, k), -jnp.inf)
        out = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1), v)
    return jnp.swapaxes(out, 0, 1).reshape(window, -1)


@pytest.mark.parametrize("span", [
    None,       # full, on grouped keys
    40,         # shorter than a key tile
    200,        # not a multiple of one
    128,        # exactly a key tile
    512])       # as long as the window
def test_fused_kernel_with_a_span_against_the_blocked_oracle(span):
    window = 512
    q, k, v = grouped_operands(window, seed=2)
    fused = attention.fused_causal_attention(
        q.astype(v.dtype), None, k.astype(v.dtype), None, v, heads=HEADS,
        span=span, query_tile=256, key_tile=128, interpret=True)
    blocked, engaged, scored = attention.grouped_causal_attention(
        q, k, v, HEADS, 64, span)
    assert engaged == 0 and fused.dtype == blocked.dtype == jnp.bfloat16
    assert scored == attention.scored_keys(window, span, 64)
    exact = exact_grouped_attention(q, k, v, HEADS, span)
    to_exact = distance(fused, exact), distance(blocked, exact)
    # both are the bfloat16 rounding of the weights and of the output;
    # neither path may be the looser by more than a quarter
    assert max(to_exact) < 0.004
    assert max(to_exact) < 1.25 * min(to_exact)
    assert distance(fused, blocked) < 0.005
    if span == 512:     # a span as long as the window masks nothing
        np.testing.assert_array_equal(
            np.asarray(fused, np.float32),
            np.asarray(attention.fused_causal_attention(
                q.astype(v.dtype), None, k.astype(v.dtype), None, v,
                heads=HEADS, query_tile=256, key_tile=128, interpret=True),
                np.float32))


@pytest.mark.parametrize("span", [40, 200, 128])
def test_fused_kernel_reads_a_span_and_no_further(span):
    """A changed key and value at position t move the queries t … t + span − 1
    and no other — the tiles before the span are not read, those inside are
    masked to it."""
    window, t = 512, 130
    q, k, v = (a.astype(jnp.bfloat16) for a in grouped_operands(window,
                                                                seed=3))
    run = functools.partial(attention.fused_causal_attention, heads=HEADS,
                            span=span, query_tile=256, key_tile=128,
                            interpret=True)
    before = np.asarray(run(q, None, k, None, v), np.float32)
    after = np.asarray(run(q, None, k.at[t].add(2.0), None,
                           v.at[t].add(2.0)), np.float32)
    changed = np.flatnonzero(np.abs(after - before).max(1) > 0)
    assert changed.min() == t and changed.max() == t + span - 1
    assert len(changed) > span // 2


@pytest.mark.parametrize("window,span,query_tile,key_tile", [
    (512, 40, 256, 128), (512, 200, 256, 128), (512, 128, 256, 128),
    (1024, 128, 256, 128), (16384, 1024, 1024, 512), (16384, None, 1024, 512),
    (4096, None, 1024, 512)])
def test_the_visited_tiles_are_those_that_hold_a_key_of_a_span(
        window, span, query_tile, key_tile):
    """``scored_keys`` — what the counter reports of the kernel — counts, a
    block of queries at a time, exactly the key tiles that hold a key some
    query of the block reads (before the diagonal: every row against each;
    along it: the rows from each tile's first on), and the kernel's loop
    starts at the first of them."""
    total = 0
    for first in range(0, window, query_tile):
        queries = range(first, first + query_tile)
        read = {j for t in (queries[0], queries[-1])
                for j in (max(0, t - span + 1) if span else 0, t)}
        tiles = [tile for tile in range(0, first + query_tile, key_tile)
                 if any(tile <= j < tile + key_tile
                        for j in range(min(read), max(read) + 1))]
        # every key between the block's earliest and latest is some query's
        earliest = (max(first - span + 1, 0) if span else 0) // key_tile
        assert tiles[0] == earliest * key_tile
        for tile in tiles:
            total += (query_tile if tile < first
                      else first + query_tile - tile) * key_tile
    assert attention.scored_keys(window, span, query_tile, key_tile) == total
    if window == 16384:
        # the published stack's figures: two full layers and six sliding
        # ones, whole tiles of 1,024 × 512
        per_token = total / window
        assert per_token == (1728 if span else 8448)
        assert 2 * 8448 + 6 * 1728 == 27264


def choice_operands(case):
    """``(heads, q, k, v)`` of a case: 4 query heads on 2 key heads (16 on 1
    where the group is to be too large to stack) at the case's head width,
    window and dtype of the values."""
    window = {"window-40": 40, "window-over-the-bound": 2
              * attention.FUSED_MAX_WINDOW}.get(
        case, attention.FUSED_QUERY_TILE)
    width = int(case.split("-")[2]) if case.startswith("head-width") else 128
    heads, key_heads = (16, 1) if case.endswith("group-16") else (4, 2)
    dtype = jnp.float32 if case.endswith("float32") else jnp.bfloat16
    q = jax.ShapeDtypeStruct((window, heads * width), jnp.float32)
    k = jax.ShapeDtypeStruct((window, key_heads * width), jnp.float32)
    return heads, q, k, jax.ShapeDtypeStruct((window, key_heads * width),
                                             dtype)


@pytest.mark.parametrize("span", [None, 256])
@pytest.mark.parametrize("case", [
    "window-40", "window-over-the-bound", "head-width-32", "head-width-96",
    "float32", "lowered-for-cpu", "lowered-for-tpu", "head-width-64",
    "head-width-64-float32", "head-width-64-lowered-for-cpu",
    "head-width-64-group-16"])
def test_the_choice_on_grouped_keys_follows_what_the_lowering_can_see(
        case, span):
    """Grouped keys, with a span or without, beside the latent model's
    cases: the kernel is taken where the program is lowered for a TPU with
    bfloat16 values, a window of whole query tiles under the bound and a head
    width of whole lanes — or of half a lane group, 64, where a key head's
    query heads are few enough to stack (4 × a block of 512 rows; 16 are
    not): with a span too, which the stacked rows are masked to as one
    head's are; the blocked path everywhere else — a CPU, float32 values, a
    head width of 32 or 96 — with the same span."""
    platform = "cpu" if case.endswith("lowered-for-cpu") else "tpu"
    heads, *operands = choice_operands(case)
    fn = jax.jit(lambda q, k, v: attention.grouped_causal_attention(
        q, k, v, heads, 512, span))
    text = fn.trace(*operands).lower(lowering_platforms=(platform,)).as_text()
    assert text.count("tpu_custom_call") == (
        case in ("lowered-for-tpu", "head-width-64"))


# -- the fused kernel at a head width of 64: a key head's query heads stacked

NARROW = 64


def narrow_operands(window, heads, key_heads, seed=0):
    """grouped_causal_attention's operands at a head width of 64: float32
    queries (carrying the scale) and keys as a rotation leaves them,
    bfloat16 values."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (window, heads * NARROW)) * 3 / 8
    k = jax.random.normal(keys[1], (window, key_heads * NARROW))
    v = jax.random.normal(keys[2], (window, key_heads * NARROW))
    return q, k, v.astype(jnp.bfloat16)


@pytest.mark.parametrize("span", [None, 40, 200])
@pytest.mark.parametrize("heads,key_heads", [(8, 2), (4, 2)])
def test_narrow_kernel_against_the_blocked_oracle(heads, key_heads, span):
    """Query heads of 64 in groups of 4 and of 2 on their key heads, the
    kernel interpreted at small tiles over a window of three blocks of
    queries — the loop over the tiles before the diagonal runs, the diagonal
    is two key tiles, the second a run of rows a stacked head — against the
    blocked path and against float32 attention."""
    window = 768
    q, k, v = narrow_operands(window, heads, key_heads, seed=2)
    fused = attention.fused_causal_attention(
        q.astype(v.dtype), None, k.astype(v.dtype), None, v, heads=heads,
        span=span, query_tile=256, key_tile=128, interpret=True)
    blocked, engaged, scored = attention.grouped_causal_attention(
        q, k, v, heads, 64, span)
    assert engaged == 0 and fused.dtype == blocked.dtype == jnp.bfloat16
    assert fused.shape == blocked.shape == (window, heads * NARROW)
    assert scored == attention.scored_keys(window, span, 64)
    exact = exact_grouped_attention(q, k, v, heads, span)
    to_exact = distance(fused, exact), distance(blocked, exact)
    # both are the bfloat16 rounding of the weights and of the output;
    # neither path may be the looser by more than a quarter
    assert max(to_exact) < 0.004
    assert max(to_exact) < 1.25 * min(to_exact)
    assert distance(fused, blocked) < 0.005


@pytest.mark.parametrize("span", [None, 200])
def test_narrow_kernel_is_causal_in_every_stacked_head(span):
    """A changed key and value at position t move, in each of a key head's
    four stacked query heads, the queries from t on — to t + span − 1 with a
    span — and no earlier one: a row's place among the queries is its place
    in its own head's block."""
    window, heads, t = 512, 8, 300      # in the second block's first key tile
    q, k, v = (a.astype(jnp.bfloat16)
               for a in narrow_operands(window, heads, 2, seed=3))
    run = functools.partial(attention.fused_causal_attention, heads=heads,
                            span=span, query_tile=256, key_tile=128,
                            interpret=True)
    before = np.asarray(run(q, None, k, None, v), np.float32)
    after = np.asarray(run(q, None, k.at[t].add(2.0), None,
                           v.at[t].add(2.0)), np.float32)
    moved = np.abs(after - before).reshape(window, heads, NARROW).max(-1) > 0
    last = window - 1 if span is None else t + span - 1
    for head in range(heads):
        changed = np.flatnonzero(moved[:, head])
        assert t <= changed.min() and changed.max() <= last
        assert len(changed) > (last - t) // 2
    # the first and the last query that read it: a head's one weight may be
    # lost to the output's rounding, every head's is not
    changed = np.flatnonzero(moved.any(1))
    assert changed.min() == t and changed.max() == last
