"""The expert layer (models/experts.py) on the CPU: the grouped products'
kernel, interpreted, against ``lax.ragged_dot``, its visits against its grid,
and which path a lowering takes; the combine's kernel, interpreted, against
the gathers, round after round, its tiles, and which path a lowering takes;
``routed_experts`` through either kernel against its default path."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np
import pytest

from sparkdl_tpu.models import experts as moe
from sparkdl_tpu.models import registry

# the expert layers of the two test stacks (their topk_eps differ)
LATENT = registry.SEQUENCE_MODELS["TestLatentMoE"].moe
SHORTCONV = registry.SEQUENCE_MODELS["TestShortConvMoE"].moe


# -- the grouped products' kernel ---------------------------------------------

HIDDEN, WIDTH = 256, 128        # whole lanes, as the kernel's blocks need


def expert_operands(groups, rows, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)

    def draw(key, *shape):
        return (jax.random.normal(key, shape, jnp.float32)
                / shape[-2] ** 0.5).astype(jnp.bfloat16)

    return (jax.random.normal(keys[0], (rows, HIDDEN)).astype(jnp.bfloat16),
            {"gate": draw(keys[1], groups, HIDDEN, WIDTH),
             "up": draw(keys[2], groups, HIDDEN, WIDTH),
             "down": draw(keys[3], groups, WIDTH, HIDDEN)})


def ragged(rows, weights, group_sizes):
    return lax.ragged_dot(rows, weights, group_sizes,
                          preferred_element_type=jnp.float32)


@pytest.mark.parametrize("tile,sub_tile", [(16, 16), (32, 16)])
@pytest.mark.parametrize("case,group_sizes,rows", [
    ("uneven-off-the-tiles", (5, 20, 7, 23, 9), 64),
    ("an-empty-group", (16, 0, 11, 0, 37), 64),
    ("a-tail-of-no-group", (5, 20, 0, 7, 0), 96),
    ("no-row-at-all", (0, 0, 0), 32)])
def test_grouped_kernel_against_ragged_dot(case, group_sizes, rows, tile,
                                           sub_tile):
    """Both uses of the kernel, interpreted, against ``lax.ragged_dot`` and
    ``silu · mul`` on its float32 results: row tiles that the groups'
    boundaries do not respect (whole, and worked in sub-tiles of which a
    visit skips those without a row of its group), empty groups (never
    visited), and whole tiles past the last group's end, which come out zero
    as ragged_dot's do."""
    x, experts = expert_operands(len(group_sizes), rows)
    group_sizes = jnp.asarray(group_sizes, jnp.int32)
    visits = moe._group_visits(group_sizes, rows, tile)
    run = functools.partial(moe.grouped_product, row_tile=tile,
                            sub_tile=sub_tile, interpret=True)
    hidden = run(x, (experts["gate"], experts["up"]), group_sizes,
                 jnp.bfloat16)
    want = (jax.nn.silu(ragged(x, experts["gate"], group_sizes))
            * ragged(x, experts["up"], group_sizes)).astype(jnp.bfloat16)
    assert hidden.dtype == jnp.bfloat16 and hidden.shape == (rows, WIDTH)
    # one rounding of the same float32 numbers: a last place at most
    np.testing.assert_allclose(np.asarray(hidden, np.float32),
                               np.asarray(want, np.float32), rtol=2 ** -7,
                               atol=1e-6)
    out = run(want, (experts["down"],), group_sizes, jnp.float32)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(out, ragged(want, experts["down"],
                                           group_sizes), rtol=1e-5, atol=1e-5)
    used = int(group_sizes.sum())
    assert not np.asarray(hidden[used:], np.float32).any()
    assert not np.asarray(out[used:]).any()
    # every (tile, group) that share a row is visited once, group by group
    group_of, tile_of, offsets, count = (np.asarray(v) for v in visits)
    starts, ends = offsets[:-2], offsets[1:-1]
    shared = {(t, g) for g in range(len(starts)) for t in range(rows // tile)
              if max(starts[g], tile * t) < min(ends[g], tile * t + tile)}
    empty = {(t, len(starts)) for t in range(-(-used // tile), rows // tile)}
    assert sorted(zip(tile_of[:count], group_of[:count])) == sorted(
        shared | empty) and count == len(shared | empty)


@pytest.mark.parametrize("sizes_,rows", [
    ([512] * 8, 4096), ([0, 700, 0, 100, 1, 511, 0], 2048),
    ([3, 0, 0], 1024), ([0, 0, 0, 0], 512), ([511, 2, 1023], 2048)])
def test_product_visits_are_the_grid_of_group_visits(sizes_, rows):
    sizes_ = jnp.asarray(sizes_, jnp.int32)
    *_, visits = moe._group_visits(sizes_, rows,
                                          moe.GROUPED_ROW_TILE)
    assert int(moe.product_visits(sizes_, rows)) == int(visits)


def test_product_visits_at_the_cells_load():
    """256 groups of 512 rows on a buffer of 131,072 (16,384 tokens × 8):
    an even split visits each tile once; a shift of one row makes every
    tile but the first and last straddle a boundary."""
    even = jnp.full((256,), 512, jnp.int32)
    assert int(moe.product_visits(even, 131072)) == 256
    shifted = even.at[0].add(-1).at[-1].add(1)
    assert int(moe.product_visits(shifted, 131072)) == 511


def product_operands(case):
    """expert_products' operands: 4 experts of 256 × 128 over a buffer of two
    row tiles, but for what the case changes."""
    rows = moe.GROUPED_ROW_TILE * 2 + (
        8 if case == "products-rows-off-the-tile" else 0)
    width = 96 if case == "products-width-off-the-lanes" else 128
    dtype = jnp.float32 if case == "products-float32" else jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(5), 4)

    def draw(key, *shape):
        return (jax.random.normal(key, shape, jnp.float32)
                / shape[-2] ** 0.5).astype(dtype)

    return (draw(keys[0], rows, 256),
            {"gate": draw(keys[1], 4, 256, width),
             "up": draw(keys[2], 4, 256, width),
             "down": draw(keys[3], 4, width, 256)},
            jnp.asarray([300, 0, 411, 200], jnp.int32))


@pytest.mark.parametrize("case", [
    "products-float32", "products-rows-off-the-tile",
    "products-width-off-the-lanes", "products-lowered-for-cpu",
    "products-lowered-for-tpu"])
def test_the_choice_follows_what_the_lowering_can_see(case):
    """The kernels are taken where the program is lowered for a TPU with
    bfloat16 operands and a buffer of whole row tiles and widths of whole
    lanes; the counter says which."""
    platform = "tpu" if case.endswith("lowered-for-tpu") else "cpu"
    operands = product_operands(case)
    fn = jax.jit(moe.expert_products)
    text = fn.trace(*operands).lower(lowering_platforms=(platform,)).as_text()
    # gate and up in one, and down
    assert text.count("tpu_custom_call") == 2 * (platform == "tpu")
    if platform == "tpu":
        return                       # nothing here can run it
    out, engaged = fn(*operands)
    assert engaged == 0
    rows, experts, group_sizes = operands
    assert out.shape == rows.shape and out.dtype == jnp.float32
    assert np.asarray(out[:911]).any() and not np.asarray(out[911:]).any()


@pytest.mark.parametrize("case", ["a-tail-of-no-group", "further-rounds",
                                  "held-whole"])
def test_routed_experts_through_the_kernel_equals_the_default_path(
        case, monkeypatch):
    """``routed_experts`` with the TPU branch taken (the kernels
    interpreted, a row tile of 16) against the ``lax.ragged_dot`` branch and
    the combine's gathers: a buffer whose tail belongs to no expert, whose
    rows the combine multiplies by nought — finite all the same, because the
    kernel zeroes them; the group sizes traced inside the rounds'
    ``fori_loop``; and a layer held whole, whose combine stays the
    gathers."""
    held = tuple(range(16)) if case == "held-whole" else (4, 5, 6, 7)
    config = dataclasses.replace(
        SHORTCONV, expert_width=WIDTH, experts_held=held,
        capacity_factor=0.5 if case == "further-rounds" else 2.0)
    x, experts = expert_operands(len(held), 96, seed=3)
    layer = {"experts": experts, "router": jax.random.normal(
        jax.random.PRNGKey(4), (HIDDEN, 16), jnp.float32)}
    x = x.astype(jnp.float32)
    want, told = jax.jit(
        lambda p, x: moe.routed_experts(p, x, config))(layer, x)
    assert told["fused_products"] == told["fused_combine"] == 0
    capacity = moe.buffer_capacity(96, config)
    assert capacity % 16 == 0
    counts = told["counts"]
    assert {"a-tail-of-no-group": int(counts.sum()) < capacity - 16,
            "further-rounds": capacity < int(counts.sum()),
            "held-whole": capacity == int(counts.sum()) == 96 * 4}[case]
    monkeypatch.setattr(moe, "GROUPED_ROW_TILE", 16)
    monkeypatch.setattr(moe, "grouped_product", functools.partial(
        moe.grouped_product, row_tile=16, interpret=True))
    monkeypatch.setattr(moe, "fused_combine", functools.partial(
        moe.fused_combine, interpret=True))
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *operands, tpu, default: tpu(*operands))
    got, again = jax.jit(
        lambda p, x: moe.routed_experts(p, x, config))(layer, x)
    # a layer held whole has a row for every pair: the combine's gathers
    assert again["fused_products"] == 1
    assert again["fused_combine"] == (case != "held-whole")
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(again["counts"], counts)
    # the two paths sum in another order, so a few of hidden's bfloat16
    # roundings fall the other way: a last place of one term of a row's sum
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


# -- the combine kernel -------------------------------------------------------


def combine_operands(tokens, hidden, top_k, rows, share, seed=0,
                     exact=False):
    """``combine``'s operands: each pair at its own row of ``out`` (rows ≥
    the pairs), a ``share`` of them held — the rest weighed by nought at a
    row anywhere, in ``out`` or not — and the first 8 tokens and token 9
    without a held pair. ``exact``: weights that are powers of two, so that
    every product is exact and only the order of the sums can tell two
    paths apart."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    pairs = tokens * top_k
    at = jax.random.permutation(keys[0], rows)[:pairs].astype(jnp.int32)
    held = jax.random.uniform(keys[1], (pairs,)) < share
    held = held.reshape(tokens, top_k).at[:8].set(False).at[9].set(False)
    weights = (2.0 ** jax.random.randint(keys[2], (pairs,), -3, 2)
               if exact else jax.random.uniform(keys[2], (pairs,)) + 0.01)
    weights = jnp.where(held.reshape(-1), weights, 0.0)
    stray = jax.random.randint(keys[3], (pairs,), -rows, 2 * rows)
    at = jnp.where(held.reshape(-1), at, stray)
    out = jax.random.normal(keys[4], (rows, hidden), jnp.float32)
    y = jax.random.normal(keys[0], (tokens, hidden), jnp.float32)
    return y, out, at, weights


def kernel_combine(y, out, at, weights, top_k, tile):
    return moe.fused_combine(
        y, out, jnp.where(weights != 0, at, -1),
        weights.reshape(-1, top_k), tile=tile, interpret=True)


@pytest.mark.parametrize("case,top_k,tile", [
    ("held-whole", 8, 16), ("held-whole", 4, 8), ("a-share", 8, 16),
    ("a-share", 4, 32), ("a-share", 4, 8)])
@pytest.mark.parametrize("exact", [True, False])
def test_fused_combine_against_the_gathers(case, top_k, tile, exact):
    """The kernel interpreted against the tiled gathers: with products that
    are exact, the same numbers to the bit (but for a zero's sign), so the
    same k order; with any weights within a last place, which is how far
    the CPU's fused multiply-adds, placed by each program's own fusions,
    can move them; a token with no held pair keeps its row of ``y``."""
    tokens, hidden = 64, 256
    share = 1.0 if case == "held-whole" else 0.3
    y, out, at, weights = combine_operands(
        tokens, hidden, top_k, tokens * top_k + 8 * (case == "a-share"),
        share, exact=exact)
    want = moe.gathered_combine(y, out, at, weights, top_k)
    got = kernel_combine(y, out, at, weights, top_k, tile)
    assert got.shape == y.shape and got.dtype == jnp.float32
    if exact:
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    fetched = np.asarray(weights != 0).reshape(tokens, top_k).any(1)
    assert fetched.all() == (case == "held-whole") or (
        not fetched[:8].any() and not fetched[9])
    np.testing.assert_array_equal(got[~fetched], y[~fetched])


@pytest.mark.parametrize("top_k", [4, 8])
def test_fused_combine_adds_round_after_round(top_k):
    """Pairs in more rows than a round's buffer holds (a capacity under the
    held pairs): each round hands the kernel its own rows and weighs the
    others' by nought; ``y`` is carried from round to round, as the rounds'
    ``fori_loop`` carries it, and ends where the gathers' rounds end."""
    tokens, hidden = 64, 128
    capacity = tokens * top_k // 2
    y, out, at, weights = combine_operands(
        tokens, hidden, top_k, 3 * capacity, 0.5, seed=1, exact=True)
    got = want = jnp.zeros_like(y)
    rounds = 0
    for lo in range(0, 3 * capacity, capacity):
        mine = jnp.where((at >= lo) & (at < lo + capacity), weights, 0.0)
        rounds += bool((mine != 0).any())
        part = out[lo:lo + capacity]
        want = moe.gathered_combine(want, part, at - lo, mine, top_k)
        got = kernel_combine(got, part, at - lo, mine, top_k, 16)
    assert rounds == 3
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("tokens,hidden,top_k,want", [
    (16384, 7680, 8, 64),           # openPangu: two tiles' rows and y, 39 MB
    (16384, 2048, 4, 128), (16384, 2304, 8, 128), (16384, 2048, 8, 128),
    (24, 128, 4, 8), (60, 128, 4, None), (16384, 7680 * 16, 8, None)])
def test_the_combines_tile_follows_the_shapes(tokens, hidden, top_k, want):
    tile = moe._combine_tile(tokens, hidden, top_k)
    assert tile == want
    if tile:
        assert 4 * tile * hidden * (2 * top_k + 4) \
            <= moe.COMBINE_BLOCK_BYTES


@pytest.mark.parametrize("case", [
    "lowered-for-tpu", "lowered-for-cpu", "held-whole-lowered-for-tpu",
    "hidden-off-the-lanes", "rows-off-the-eights", "tokens-off-the-tiles"])
def test_the_combine_follows_what_the_lowering_can_see(case):
    """The combine kernel is taken where the program is lowered for a TPU,
    the buffer has fewer rows than there are pairs (a share of the experts
    is held) and ``hidden`` is whole lanes and tokens and buffer rows whole
    eights; the count says which; everywhere else the gathers — also where
    the buffer has a row for every pair (a layer held whole)."""
    platform = "tpu" if case.endswith("lowered-for-tpu") else "cpu"
    tokens = 60 if case == "tokens-off-the-tiles" else 64
    hidden = 96 if case == "hidden-off-the-lanes" else 128
    rows = {"held-whole-lowered-for-tpu": tokens * 4,
            "rows-off-the-eights": tokens * 4 - 4}.get(case, tokens * 2)
    y, out, at, weights = combine_operands(tokens, hidden, 4, tokens * 4,
                                           0.4)
    operands = y, out[:rows], at, jnp.where(at < rows, weights, 0.0)
    fn = jax.jit(lambda *a: moe.combine(*a, 4))
    text = fn.trace(*operands).lower(lowering_platforms=(platform,)).as_text()
    kernel = case == "lowered-for-tpu"
    assert text.count("tpu_custom_call") == kernel
    assert ("fused_combine" in text) == kernel
    if platform == "tpu":
        return                       # nothing here can run it
    y, engaged = fn(*operands)
    assert engaged == 0
    np.testing.assert_array_equal(
        y, moe.gathered_combine(*operands, 4))


@pytest.mark.parametrize("factor", [2.0, 0.25])
def test_routed_experts_combine_through_the_kernel(factor, monkeypatch):
    """``routed_experts`` over a share of the experts with the TPU branch
    taken and the combine kernel interpreted — float32 experts, so the
    products stay ``lax.ragged_dot`` and the combine is all that changes —
    in one round and in several: the default path's numbers, and the
    count 1 where it was 0."""
    hidden, width, held = 128, 64, (4, 5, 6, 7)
    config = dataclasses.replace(LATENT, expert_width=width,
                                 experts_held=held, capacity_factor=factor)
    keys = jax.random.split(jax.random.PRNGKey(12), 5)
    layer = {"router": jax.random.normal(keys[0], (hidden, 16)),
             "experts": {
                 "gate": jax.random.normal(keys[1], (4, hidden, width)) / 11,
                 "up": jax.random.normal(keys[2], (4, hidden, width)) / 11,
                 "down": jax.random.normal(keys[3], (4, width, hidden)) / 8}}
    x = jax.random.normal(keys[4], (64, hidden))

    def run():
        return jax.jit(lambda p, x: moe.routed_experts(p, x, config))(
            layer, x)

    want, told = run()
    assert told["fused_products"] == told["fused_combine"] == 0
    capacity = moe.buffer_capacity(64, config)
    assert (capacity < int(told["counts"].sum())) == (factor < 1)
    monkeypatch.setattr(moe, "fused_combine", functools.partial(
        moe.fused_combine, interpret=True))
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *operands, tpu, default: tpu(*operands))
    got, again = run()
    assert again["fused_products"] == 0 and again["fused_combine"] == 1
    np.testing.assert_array_equal(again["counts"], told["counts"])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
