"""The scorer's head (models/scoring_head.py) on the CPU: the fused head,
interpreted, against the path that writes its float32 logits — the next id's
logit in any tile, the sums rescaled under a later maximum, nought for an id
outside the rows —, its blocks' rule, which path a lowering takes, and
``score_head`` through either."""

import functools

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np
import pytest

from sparkdl_tpu.models import scoring_head


def head_operands(positions, hidden, rows, seed=0):
    """``fused_scoring_head``'s operands: bfloat16 positions and head, logits
    of a few units, and the id after each position."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (positions, hidden)).astype(jnp.bfloat16)
    head = (jax.random.normal(keys[1], (rows, hidden)) * 3 * hidden ** -0.5
            ).astype(jnp.bfloat16)
    return x, head, jax.random.randint(keys[2], (positions,), 0, rows)


def written_head(x, head, following):
    """The default path's arithmetic: the float32 logits whole,
    ``log_softmax``, a gather."""
    logits = jnp.dot(x, head.T, preferred_element_type=jnp.float32)
    return jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                               following[:, None], -1)[:, 0]


@pytest.mark.parametrize("positions,hidden,rows,block,tile", [
    (64, 128, 512, 16, 128),        # four blocks of positions × four tiles
    (64, 128, 384, 32, 128),        # rows 3 × a power of two
    (32, 128, 384, 32, 384),        # one block, one tile
    (32, 64, 9600, 16, 640),        # rows 75 × 128, as 19,200 is 75 × 256
    (48, 256, 1024, 8, 256)])
def test_fused_head_against_the_written_logits(positions, hidden, rows,
                                               block, tile):
    x, head, following = head_operands(positions, hidden, rows)
    got = scoring_head.fused_scoring_head(x, head, following, block=block,
                                        tile=tile, interpret=True)
    assert got.shape == (positions,) and got.dtype == jnp.float32
    # the same float32 products, summed tile by tile in another order
    np.testing.assert_allclose(got, written_head(x, head, following),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("place", ["first-tile", "middle-tile", "last-tile",
                                   "first-and-last-row"])
def test_fused_head_picks_the_next_ids_logit_in_any_tile(place):
    rows, tile = 512, 128
    x, head, _ = head_operands(32, 128, rows, seed=1)
    first = {"first-tile": 0, "middle-tile": 2 * tile,
             "last-tile": rows - tile}.get(place)
    following = (jnp.arange(32) % 2 * (rows - 1) if first is None
                 else first + jnp.arange(32) * 5 % tile).astype(jnp.int32)
    got = scoring_head.fused_scoring_head(x, head, following, block=16,
                                        tile=tile, interpret=True)
    np.testing.assert_allclose(got, written_head(x, head, following),
                               rtol=1e-5, atol=1e-5)
    # the pick is the logit itself, no sum: with the normaliser back it is
    # the product's float32 to a last place
    logits = jnp.dot(x, head.T, preferred_element_type=jnp.float32)
    np.testing.assert_allclose(
        got + jax.nn.logsumexp(logits, -1),
        jnp.take_along_axis(logits, following[:, None], -1)[:, 0],
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("where", ["last-tile", "first-tile", "every-other"])
def test_fused_head_rescales_the_sums_under_a_later_maximum(where):
    """A row whose maximum sits in the last tile, 30 above the other logits'
    few units: every earlier tile's sum is rescaled under it; in the first
    tile: no later one moves it; and rows of either kind in one block."""
    rows, tile = 512, 128
    x, head, following = head_operands(32, 128, rows, seed=2)
    early = {"last-tile": jnp.zeros((32,), bool),
             "first-tile": jnp.ones((32,), bool),
             "every-other": jnp.arange(32) % 2 == 0}[where]
    # two directions of hidden kept for the peaks: row 7 of the head answers
    # to the one, its fifth row from the last to the other
    head = head.at[:, :2].set(0).at[7, 0].set(6).at[rows - 5, 1].set(6)
    x = x.at[:, 0].set(jnp.where(early, 6, 0)).at[:, 1].set(
        jnp.where(early, 0, 6))
    logits = jnp.dot(x, head.T, preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(jnp.argmax(logits, -1),
                                  jnp.where(early, 7, rows - 5))
    got = scoring_head.fused_scoring_head(x, head, following, block=16,
                                        tile=tile, interpret=True)
    np.testing.assert_allclose(got, written_head(x, head, following),
                               rtol=1e-5, atol=1e-5)


def test_fused_head_reads_nought_for_an_id_outside_the_rows():
    """No column meets it, so what is left is the normaliser; ``score_head``
    never shows it: such a window comes back not a number."""
    x, head, following = head_operands(16, 128, 256, seed=3)
    got = scoring_head.fused_scoring_head(
        x, head, following.at[3].set(256).at[4].set(-1), block=16, tile=128,
        interpret=True)
    logits = jnp.dot(x, head.T, preferred_element_type=jnp.float32)
    np.testing.assert_allclose(got[3:5], -jax.nn.logsumexp(logits[3:5], -1),
                               rtol=1e-5)


@pytest.mark.parametrize("window,hidden,rows,dtype,want", [
    # the three published heads: blocks that divide, within the budget
    (4096, 2048, 65536, jnp.bfloat16, True),
    (16384, 2304, 98304, jnp.bfloat16, True),       # 2**15 × 3
    (4096, 7680, 19200, jnp.bfloat16, True),        # 2**8 × 75
    (768, 128, 640, jnp.bfloat16, True),            # blocks of 256, one tile
    (4096, 2048, 65536, jnp.float32, False),
    (4096, 2048, 65000, jnp.bfloat16, False),       # no tile of whole lanes
    (4096, 2000, 65536, jnp.bfloat16, False),
    (24, 128, 256, jnp.bfloat16, False),            # no block divides it
    (4096 + 128, 128, 256, jnp.bfloat16, False)])
def test_the_heads_blocks_follow_the_shapes(window, hidden, rows, dtype,
                                            want):
    blocks = scoring_head._head_blocks(window, hidden, rows, dtype)
    assert (blocks is not None) == want
    if want:
        block, tile = blocks
        assert window % block == 0 and block >= 256
        assert rows % tile == 0 and tile % 128 == 0
        assert (4 * hidden * (block + tile) + 4 * block * tile
                <= scoring_head.HEAD_BLOCK_BYTES)


def head_parameters(rows, hidden, dtype=jnp.bfloat16, seed=4):
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    head = (jax.random.normal(keys[0], (rows, hidden)) * 3 * hidden ** -0.5
            ).astype(dtype)
    return {"head": head, "embed": head,
            "final_norm": 1 + 0.1 * jax.random.normal(keys[1], (hidden,))}


@pytest.mark.parametrize("case", [
    "lowered-for-tpu", "lowered-for-cpu", "float32", "rows-off-the-lanes",
    "window-off-the-blocks"])
def test_the_head_follows_what_the_lowering_can_see(case):
    """The fused head is taken where the program is lowered for a TPU with a
    bfloat16 head of whole lanes and a window of whole blocks; the count
    says which."""
    platform = "tpu" if case == "lowered-for-tpu" else "cpu"
    window = 300 if case == "window-off-the-blocks" else 256
    params = head_parameters(
        200 if case == "rows-off-the-lanes" else 256, 128,
        jnp.float32 if case == "float32" else jnp.bfloat16)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, window, 128))
    tokens = np.random.default_rng(6).integers(
        0, params["head"].shape[0], (2, window)).astype(np.int32)
    fn = jax.jit(lambda p, h, t: scoring_head.score_head(p, h, t, 1e-5))
    text = fn.trace(params, h, tokens).lower(
        lowering_platforms=(platform,)).as_text()
    assert text.count("tpu_custom_call") == (platform == "tpu")
    assert ("fused_scoring_head" in text) == (platform == "tpu")
    if platform == "tpu":
        return                       # nothing here can run it
    out, engaged = fn(params, h, tokens)
    assert engaged.tolist() == [0, 0]
    assert out["logprobs"].shape == (2, window)
    assert not np.asarray(out["logprobs"][:, -1]).any()


def take_the_kernel(monkeypatch, block=16, tile=128):
    """Every ``lax.platform_dependent`` takes its TPU branch, and the head's
    is the kernel interpreted at blocks a small window has."""
    monkeypatch.setattr(scoring_head, "_head_blocks",
                        lambda *shapes: (block, tile))
    monkeypatch.setattr(scoring_head, "fused_scoring_head", functools.partial(
        scoring_head.fused_scoring_head, interpret=True))
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *operands, tpu, default: tpu(*operands))


def test_score_head_through_the_kernel_equals_the_default_path(monkeypatch):
    """``score_head`` with the TPU branch taken (the kernel interpreted,
    blocks of 16 positions, tiles of 128 rows) against the branch that
    writes the logits: the same numbers, the last position 0, a window with
    an id outside the rows not a number in both, ``pooled`` untouched."""
    params = head_parameters(384, 128)
    h = jax.random.normal(jax.random.PRNGKey(7), (3, 32, 128))
    tokens = np.random.default_rng(8).integers(0, 384, (3, 32)).astype(
        np.int32)
    tokens[1, 5] = 384
    fn = jax.jit(lambda p, h, t: scoring_head.score_head(p, h, t, 1e-5))
    want, engaged = fn(params, h, tokens)
    assert engaged.tolist() == [0, 0, 0]
    take_the_kernel(monkeypatch)
    got, engaged = jax.jit(
        lambda p, h, t: scoring_head.score_head(p, h, t, 1e-5))(
            params, h, tokens)
    assert engaged.tolist() == [1, 1, 1]
    np.testing.assert_allclose(got["logprobs"], want["logprobs"], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(got["pooled"], want["pooled"])
    logprobs = np.asarray(got["logprobs"])
    assert np.isnan(logprobs[1]).all() and np.isnan(got["pooled"][1]).all()
    assert np.isfinite(logprobs[[0, 2]]).all()
    assert not logprobs[[0, 2], -1].any()
