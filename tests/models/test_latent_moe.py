"""The latent-attention sparse-expert scorer (models/latent_moe.py) against
the benchmark's plain reference (benchmarks/references/openpangu_moe.py, which
imports nothing of the program) at small sizes on the CPU: through the
transformer and collect(); attention alone; the share test; no pair dropped;
the program's counts in telemetry; the benchmark's FLOP count by hand. And the
fused attention kernel, interpreted on the CPU at the published head widths,
against the blocked path that stays and a float32 soft-max; and which of the
two a lowering takes. And the fused head, interpreted, against the path that
writes its float32 logits; its blocks' rule; ``score_head`` through either."""

import dataclasses
import functools
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu.core import telemetry
from sparkdl_tpu.engine.dataframe import DataFrame
from sparkdl_tpu.ml import DeepSequenceScorer
from sparkdl_tpu.models import latent_moe, registry

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "benchmarks")


def _load(relative):
    """A module of benchmarks/ by its file, so that nothing of benchmarks/
    lands on sys.path (its module names are short: check, run, traffic)."""
    name = "bench_" + relative[:-3].replace("/", "_")
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, relative))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load("references/openpangu_moe.py")
CONFIG = json.load(open(os.path.join(
    BENCH, "tests", "rehearsal", "configs", "testmoe-windows.json")))
MODEL = registry.SEQUENCE_MODELS["TestLatentMoE"]
WINDOW = 24


def sizes(**changes):
    return ref.sizes(dict(CONFIG, **changes))


def make_variables(key, s):
    return {**ref.init_embed(key, s), **ref.init_head(key, s),
            "layers": [ref.init_layer(key, s, i, i < s.dense_layers)
                       for i in range(s.layers)]}


def tokens_of(seed, rows, vocab=32):
    return np.random.default_rng(seed).integers(
        0, vocab, size=(rows, WINDOW)).astype(np.int32)


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(11)


def test_scorer_matches_reference_through_transformer_and_collect(key):
    s = sizes()
    tokens = tokens_of(1, 7)
    frame = DataFrame.fromArrow(pa.table({
        "id": pa.array(np.arange(7)),
        "tokens": pa.array(list(tokens), type=pa.list_(pa.int32()))}),
        numPartitions=2)
    scorer = DeepSequenceScorer(
        inputCol="tokens", modelName="TestLatentMoE",
        weights=make_variables(key, s), expertsHeld=CONFIG["experts_held"],
        window=WINDOW, batchSize=2, expertCountsCol="experts")
    rows = sorted(scorer.transform(frame).collect(), key=lambda r: r["id"])
    with jax.default_matmul_precision("highest"):
        pooled, logprobs, chosen = ref.forward(key, s, tokens)
    assert [r["tokens"] for r in rows] == tokens.tolist()
    np.testing.assert_allclose([r["pooled"] for r in rows], pooled,
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose([r["logprobs"] for r in rows], logprobs,
                               rtol=2e-4, atol=2e-5)
    assert all(r["logprobs"][-1] == 0.0 for r in rows)
    counts = np.stack([np.bincount(row.ravel(), minlength=16)
                       for row in chosen[0]])
    assert np.array_equal(np.asarray([r["experts"] for r in rows]), counts)


def test_latent_attention_alone_matches_reference(key):
    s = sizes()
    p = ref.init_layer(key, s, 0, True)["attn"]
    x = jax.random.normal(jax.random.PRNGKey(5), (WINDOW, s.hidden))
    config = dataclasses.replace(MODEL, query_block=8)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(p, x, s, lambda a: a, block=5)
        got, fused = latent_moe.latent_attention(p, x, config)
    assert fused == 0
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # causal: a later token does not move an earlier one
    with jax.default_matmul_precision("highest"):
        moved, _ = latent_moe.latent_attention(p, x.at[-1].add(1.0), config)
    np.testing.assert_allclose(moved[:-1], got[:-1], rtol=1e-5, atol=1e-6)


def test_shares_add_up_to_the_uncut_layer(key):
    """Over the four shares of the 16 experts, the routed parts of all shares
    plus the shared expert, counted once, are the uncut reference's layer."""
    everything = tuple(range(16))
    s = sizes(experts_held=list(everything))
    x = jax.random.normal(jax.random.PRNGKey(6), (40, s.hidden))
    with jax.default_matmul_precision("highest"):
        whole = ref.init_layer(key, s, 2, False)["moe"]
        want, _ = ref.expert_layer(whole, x, s, lambda a: a)
        total = latent_moe.gated_mlp(whole["shared"], x)
        pairs = 0
        for share in (everything[i:i + 4] for i in range(0, 16, 4)):
            layer = ref.init_layer(key, s, 2, False, experts_held=share)
            # an expert's weights are its own, whichever share holds it
            np.testing.assert_array_equal(
                layer["moe"]["experts"]["up"],
                whole["experts"]["up"][share[0]:share[0] + 4])
            config = dataclasses.replace(MODEL, experts_held=share)
            part, _, counts, *_ = latent_moe.routed_experts(layer["moe"], x,
                                                            config)
            # ... and the reference given the same share gives the same part
            ref_part, _ = ref.routed_part(layer["moe"], x, s, lambda a: a,
                                          experts_held=share)
            np.testing.assert_allclose(part, ref_part, rtol=1e-4, atol=1e-5)
            total = total + part
            pairs += int(counts.sum())
    assert pairs == 40 * 4          # every (token, expert) pair, exactly once
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_no_pair_is_dropped_under_a_skewed_router(key):
    """One held expert gets every token: the held experts' pairs pass the
    buffer, are computed in a further round all the same, and are counted."""
    s = sizes()
    layer = ref.init_layer(key, s, 2, False)["moe"]
    layer["router"] = layer["router"].at[:, 5].add(4.0 / s.hidden ** 0.5)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (96, s.hidden)))
    config = dataclasses.replace(MODEL, experts_held=(4, 5, 6, 7),
                                 capacity_factor=1.0)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.routed_part(layer, x, s, lambda a: a)
        got, chosen, counts, overflow, *_ = jax.jit(
            lambda p, x: latent_moe.routed_experts(p, x, config))(layer, x)
    capacity = 96                   # 1.0 × 96 tokens × 4 pairs × 4 of 16
    assert int(counts[1]) == 96     # every token chose expert 5
    assert int(overflow.sum()) == int(counts.sum()) - capacity > 0
    assert int(counts.sum()) == int(np.isin(chosen, (4, 5, 6, 7)).sum())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_program_counts_reach_telemetry_and_not_the_caller(key):
    s = sizes()
    model = registry.build_sequence_scorer(
        "TestLatentMoE", make_variables(key, s), WINDOW,
        experts_held=CONFIG["experts_held"])
    from sparkdl_tpu.core import executor

    tokens = tokens_of(2, 3)
    with telemetry.Telemetry(name="t", out_dir="") as scope:
        out = executor.execute(model, tokens, batch_size=2)
        snapshot = scope.metrics.snapshot()
    assert set(out) == {"pooled", "logprobs", "expert_counts"}
    counters = snapshot["counters"]
    assert counters[telemetry.M_SEQUENCE_TOKENS] == 3 * WINDOW
    # a window of 24 fits no tile, and this is a CPU: the blocked path
    assert counters[telemetry.M_SEQUENCE_FUSED_ATTENTION_LAYERS] == 0
    assert counters[telemetry.M_SEQUENCE_FUSED_HEAD_WINDOWS] == 0
    assert counters[telemetry.M_MOE_ROUTED_TOKENS] == 3 * WINDOW
    held = np.asarray(out["expert_counts"])[:, 0, 4:8].sum()
    assert counters[telemetry.M_MOE_LOCAL_PAIRS] == held
    assert counters[telemetry.M_MOE_OVERFLOW_PAIRS] >= 0
    assert counters[telemetry.M_MOE_FUSED_PRODUCT_LAYERS] == 0
    assert counters[telemetry.M_MOE_FUSED_COMBINE_LAYERS] == 0
    ratio = snapshot["histograms"][telemetry.M_MOE_LOAD_MAX_OVER_MEAN]
    assert ratio["count"] == 3 and ratio["min"] >= 1.0
    # without a scope the outputs are the same and nothing is recorded
    again = executor.execute(model, tokens, batch_size=2)
    assert set(again) == set(out)
    np.testing.assert_array_equal(again["pooled"], out["pooled"])


def test_bfloat16_weights_are_taken_as_they_are(key):
    s = sizes()
    variables = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                             make_variables(key, s))
    model = registry.build_sequence_scorer(
        "TestLatentMoE", variables, WINDOW,
        experts_held=CONFIG["experts_held"])
    cast = model.with_dtype("bfloat16")
    assert cast.variables is variables          # no second copy
    assert not hasattr(cast, "float_source")
    tokens = tokens_of(3, 2, vocab=32) + 0      # ids above bfloat16's reach
    out = cast.apply_batch(tokens, batch_size=2)
    with jax.default_matmul_precision("highest"):
        pooled, logprobs, _ = ref.forward(key, s, tokens)
    assert out["pooled"].dtype == np.float32
    assert out["expert_counts"].dtype == np.int32   # counts are not cast
    assert np.abs(out["logprobs"] - logprobs).max() < 0.15
    assert np.abs(out["pooled"] - pooled).max() < 0.1


def test_an_id_outside_the_slice_gives_no_number(key):
    s = sizes()
    model = registry.build_sequence_scorer(
        "TestLatentMoE", make_variables(key, s), WINDOW,
        experts_held=CONFIG["experts_held"])
    tokens = tokens_of(4, 3)
    tokens[1, 5] = 32                       # the slice holds ids 0..31
    out = model.apply_batch(tokens, batch_size=4)
    assert np.isnan(out["pooled"][1]).all()
    assert np.isnan(out["logprobs"][1]).all()
    assert np.isfinite(out["pooled"][[0, 2]]).all()
    assert np.isfinite(out["logprobs"][[0, 2]]).all()


def test_builder_reads_the_share_off_the_weights(key):
    s = sizes()
    variables = make_variables(key, s)
    with pytest.raises(ValueError, match="experts_held names 16"):
        registry.build_sequence_scorer("TestLatentMoE", variables, WINDOW)
    with pytest.raises(ValueError, match="Unsupported sequence model"):
        registry.build_sequence_scorer("NoSuchModel", variables, WINDOW)
    model = registry.build_sequence_scorer(
        "TestLatentMoE", variables, WINDOW, experts_held=(4, 5, 6, 7))
    assert model.input_spec.shape == (None, WINDOW)
    assert model.input_spec.dtype == "int32"


# -- the fused kernel and the choice -----------------------------------------

HEADS, NOPE, ROPE, WIDTH = 4, 128, 64, 128       # the published head widths


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


def exact_attention(q_nope, q_rope, k_nope, k_rope, v):
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


def distance(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("window,query_tile", [(256, 128), (512, 128),
                                               (512, 256)])
def test_fused_kernel_against_the_blocked_path_and_float32(window,
                                                           query_tile):
    operands = attention_operands(window)
    fused = latent_moe.fused_causal_attention(
        *operands, query_tile=query_tile, key_tile=128, interpret=True)
    blocked, engaged = latent_moe.causal_attention(*operands, 128)
    assert engaged == 0 and fused.dtype == blocked.dtype == jnp.bfloat16
    exact = exact_attention(*operands)
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
    run = functools.partial(latent_moe.fused_causal_attention,
                            query_tile=128, key_tile=128, interpret=True)
    before = np.asarray(run(q_nope, q_rope, k_nope, k_rope, v), np.float32)
    after = np.asarray(run(
        q_nope, q_rope, k_nope.at[-1].add(3.0), k_rope.at[-1].add(3.0),
        v.at[-1].add(3.0)), np.float32)
    np.testing.assert_array_equal(after[:-1], before[:-1])
    assert np.abs(after[-1] - before[-1]).max() > 0.1


def product_operands(case):
    """expert_products' operands: 4 experts of 256 × 128 over a buffer of two
    row tiles, but for what the case changes."""
    rows = latent_moe.GROUPED_ROW_TILE * 2 + (
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
    "window-24", "float32", "lowered-for-cpu", "lowered-for-tpu",
    "products-float32", "products-rows-off-the-tile",
    "products-width-off-the-lanes", "products-lowered-for-cpu",
    "products-lowered-for-tpu"])
def test_the_choice_follows_what_the_lowering_can_see(case):
    """A kernel is taken where the program is lowered for a TPU with bfloat16
    operands and shapes of whole tiles — attention's window, the grouped
    products' buffer and widths; the counter says which."""
    platform = "tpu" if case.endswith("lowered-for-tpu") else "cpu"
    if case.startswith("products-"):
        operands = product_operands(case)
        fn = jax.jit(latent_moe.expert_products)
        calls = 2                        # gate and up in one, and down
    else:
        window = 24 if case == "window-24" else latent_moe.FUSED_QUERY_TILE
        dtype = jnp.float32 if case == "float32" else jnp.bfloat16
        operands = attention_operands(window, dtype)
        fn = jax.jit(lambda *a: latent_moe.causal_attention(*a, 512))
        calls = 1
    text = fn.trace(*operands).lower(lowering_platforms=(platform,)).as_text()
    assert text.count("tpu_custom_call") == calls * (platform == "tpu")
    if platform == "tpu":
        return                       # nothing here can run it
    out, engaged = fn(*operands)
    assert engaged == 0
    if case.startswith("products-"):
        rows, experts, group_sizes = operands
        assert out.shape == rows.shape and out.dtype == jnp.float32
        assert np.asarray(out[:911]).any() and not np.asarray(out[911:]).any()
        return
    assert out.shape == (window, HEADS * WIDTH)
    assert distance(out, exact_attention(*operands)) < 0.003


# -- the combine kernel ---------------------------------------------------------


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
    return latent_moe.fused_combine(
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
    want = latent_moe.gathered_combine(y, out, at, weights, top_k)
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
        want = latent_moe.gathered_combine(want, part, at - lo, mine, top_k)
        got = kernel_combine(got, part, at - lo, mine, top_k, 16)
    assert rounds == 3
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("tokens,hidden,top_k,want", [
    (16384, 7680, 8, 64),           # openPangu: two tiles' rows and y, 39 MB
    (16384, 2048, 4, 128), (16384, 2304, 8, 128), (16384, 2048, 8, 128),
    (24, 128, 4, 8), (60, 128, 4, None), (16384, 7680 * 16, 8, None)])
def test_the_combines_tile_follows_the_shapes(tokens, hidden, top_k, want):
    tile = latent_moe._combine_tile(tokens, hidden, top_k)
    assert tile == want
    if tile:
        assert 4 * tile * hidden * (2 * top_k + 4) \
            <= latent_moe.COMBINE_BLOCK_BYTES


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
    fn = jax.jit(lambda *a: latent_moe.combine(*a, 4))
    text = fn.trace(*operands).lower(lowering_platforms=(platform,)).as_text()
    kernel = case == "lowered-for-tpu"
    assert text.count("tpu_custom_call") == kernel
    assert ("fused_combine" in text) == kernel
    if platform == "tpu":
        return                       # nothing here can run it
    y, engaged = fn(*operands)
    assert engaged == 0
    np.testing.assert_array_equal(
        y, latent_moe.gathered_combine(*operands, 4))


@pytest.mark.parametrize("factor", [2.0, 0.25])
def test_routed_experts_combine_through_the_kernel(factor, monkeypatch):
    """``routed_experts`` over a share of the experts with the TPU branch
    taken and the combine kernel interpreted — float32 experts, so the
    products stay ``lax.ragged_dot`` and the combine is all that changes —
    in one round and in several: the default path's numbers, and the
    count 1 where it was 0."""
    hidden, width, held = 128, 64, (4, 5, 6, 7)
    config = dataclasses.replace(MODEL, hidden=hidden, expert_width=width,
                                 experts_held=held, capacity_factor=factor)
    keys = jax.random.split(jax.random.PRNGKey(12), 5)
    layer = {"router": jax.random.normal(keys[0], (hidden, 16)),
             "experts": {
                 "gate": jax.random.normal(keys[1], (4, hidden, width)) / 11,
                 "up": jax.random.normal(keys[2], (4, hidden, width)) / 11,
                 "down": jax.random.normal(keys[3], (4, width, hidden)) / 8}}
    x = jax.random.normal(keys[4], (64, hidden))

    def run():
        return jax.jit(lambda p, x: latent_moe.routed_experts(p, x, config))(
            layer, x)

    want, _, counts, _, fused, fused_combine = run()
    assert fused == fused_combine == 0
    capacity = latent_moe.buffer_capacity(64, config)
    assert (capacity < int(counts.sum())) == (factor < 1)
    monkeypatch.setattr(latent_moe, "fused_combine", functools.partial(
        latent_moe.fused_combine, interpret=True))
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *operands, tpu, default: tpu(*operands))
    got, _, again, _, fused, fused_combine = run()
    assert fused == 0 and fused_combine == 1
    np.testing.assert_array_equal(again, counts)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# -- the fused head -------------------------------------------------------------


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
    got = latent_moe.fused_scoring_head(x, head, following, block=block,
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
    got = latent_moe.fused_scoring_head(x, head, following, block=16,
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
    got = latent_moe.fused_scoring_head(x, head, following, block=16,
                                        tile=tile, interpret=True)
    np.testing.assert_allclose(got, written_head(x, head, following),
                               rtol=1e-5, atol=1e-5)


def test_fused_head_reads_nought_for_an_id_outside_the_rows():
    """No column meets it, so what is left is the normaliser; ``score_head``
    never shows it: such a window comes back not a number."""
    x, head, following = head_operands(16, 128, 256, seed=3)
    got = latent_moe.fused_scoring_head(
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
    blocks = latent_moe._head_blocks(window, hidden, rows, dtype)
    assert (blocks is not None) == want
    if want:
        block, tile = blocks
        assert window % block == 0 and block >= 256
        assert rows % tile == 0 and tile % 128 == 0
        assert (4 * hidden * (block + tile) + 4 * block * tile
                <= latent_moe.HEAD_BLOCK_BYTES)


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
    fn = jax.jit(lambda p, h, t: latent_moe.score_head(p, h, t, 1e-5))
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
    monkeypatch.setattr(latent_moe, "_head_blocks",
                        lambda *shapes: (block, tile))
    monkeypatch.setattr(latent_moe, "fused_scoring_head", functools.partial(
        latent_moe.fused_scoring_head, interpret=True))
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
    fn = jax.jit(lambda p, h, t: latent_moe.score_head(p, h, t, 1e-5))
    want, engaged = fn(params, h, tokens)
    assert engaged.tolist() == [0, 0, 0]
    take_the_kernel(monkeypatch)
    got, engaged = jax.jit(
        lambda p, h, t: latent_moe.score_head(p, h, t, 1e-5))(
            params, h, tokens)
    assert engaged.tolist() == [1, 1, 1]
    np.testing.assert_allclose(got["logprobs"], want["logprobs"], rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(got["pooled"], want["pooled"])
    logprobs = np.asarray(got["logprobs"])
    assert np.isnan(logprobs[1]).all() and np.isnan(got["pooled"][1]).all()
    assert np.isfinite(logprobs[[0, 2]]).all()
    assert not logprobs[[0, 2], -1].any()


def test_the_stack_counts_the_windows_its_head_fused(key, monkeypatch):
    """The latent-attention stack's forward with a head of 128 rows in
    bfloat16: the count reads 0 a window on the path that writes the logits
    and 1 through the kernel, and the log-probabilities agree."""
    s = sizes(vocab_size=128)
    variables = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                             make_variables(key, s))
    config = dataclasses.replace(MODEL, vocab=128,
                                 experts_held=tuple(CONFIG["experts_held"]))
    tokens = tokens_of(9, 2, vocab=128)

    def run():
        out = jax.jit(lambda p, t: latent_moe.forward(p, t, config))(
            variables, tokens)
        return out, out[telemetry.PROGRAM_COUNTS][
            telemetry.M_SEQUENCE_FUSED_HEAD_WINDOWS].tolist()

    want, count = run()
    assert count == [0, 0]
    # only the head's choice is turned: a window of 24 gives attention and
    # the experts' products no tiles, so they lower what they lowered
    take_the_kernel(monkeypatch, block=8)
    got, count = run()
    assert count == [1, 1]
    assert got[telemetry.PROGRAM_COUNTS][
        telemetry.M_SEQUENCE_FUSED_ATTENTION_LAYERS].tolist() == [0, 0]
    np.testing.assert_allclose(got["logprobs"], want["logprobs"], rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_array_equal(got["pooled"], want["pooled"])


def test_importing_the_registry_loads_no_pallas():
    """The image cells import models.registry too: the kernel's toolkit is
    imported where the kernel is built, not with the module."""
    code = ("import sys, sparkdl_tpu.models.registry; "
            "sys.exit('jax.experimental.pallas' in sys.modules)")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=os.path.dirname(BENCH)).returncode == 0


@pytest.mark.parametrize("window", [16, 32])
def test_flops_lm_against_a_hand_count(window):
    flops_lm = _load("flops_lm.py")
    attention = 64 * 32 + 32 * 4 * 24 + 64 * 24 + 16 * 4 * 32 + 4 * 16 * 64
    pairs = window * (window + 1) // 2
    dense = attention * window + 4 * pairs * 40 + window * 3 * 64 * 128
    expert = 3 * 64 * 32
    moe = (attention * window + 4 * pairs * 40 + window * 64 * 16
           + window * expert + window * 4 * (4 / 16) * expert)
    head = (window - 1) * 64 * 32
    assert flops_lm.window_flops(CONFIG, window) == 2 * (2 * dense + moe
                                                         + head)


def test_token_traffic_is_the_seeds():
    traffic = _load("token_traffic.py")
    params = {"n": 4, "window": 64, "vocab": 50, "exponent": 1.0}
    a = traffic.token_windows(params, 2**31 + 5)
    assert a.dtype == np.int32 and a.shape == (4, 64)
    assert (a == traffic.token_windows(params, 2**31 + 5)).all()
    assert not (a == traffic.token_windows(params, 2**31 + 6)).all()
    assert 0 <= a.min() and a.max() < 50
