"""The short-convolution sparse-expert scorer (models/shortconv_moe.py) and
the expert layer it shares with the latent-attention one, against the
benchmark's plain reference (benchmarks/references/lfm2_moe.py, which imports
nothing of the program) at small sizes on the CPU: through the transformer and
collect(); the short convolution alone, and that it is causal; grouped
attention with per-head norms alone; the selection bias; the share test; no
pair dropped and a buffer of exactly the pairs when all are held; the combine
over one round and several; the program's counts in telemetry; the benchmark's FLOP
count by hand."""

import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu.core import telemetry
from sparkdl_tpu.engine.dataframe import DataFrame
from sparkdl_tpu.ml import DeepSequenceScorer
from sparkdl_tpu.models import latent_moe, registry, shortconv_moe

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


ref = _load("references/lfm2_moe.py")
CONFIG = json.load(open(os.path.join(
    BENCH, "tests", "rehearsal", "configs", "testshortconv-windows.json")))
MODEL = registry.SEQUENCE_MODELS["TestShortConvMoE"]
WINDOW = 24


def identity(a):
    return a


def sizes(**changes):
    return ref.sizes(dict(CONFIG, **changes))


def make_variables(key, s):
    embed = ref.init_embed(key, s)["embed"]
    return {"embed": embed, "head": embed,
            "final_norm": ref.init_head(key, s)["final_norm"],
            "layers": [ref.init_layer(key, s, i, i < s.dense_layers)
                       for i in range(s.layers)]}


def tokens_of(seed, rows, vocab=32):
    return np.random.default_rng(seed).integers(
        0, vocab, size=(rows, WINDOW)).astype(np.int32)


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(13)


def test_scorer_matches_reference_through_transformer_and_collect(key):
    s = sizes()
    tokens = tokens_of(1, 7)
    frame = DataFrame.fromArrow(pa.table({
        "id": pa.array(np.arange(7)),
        "tokens": pa.array(list(tokens), type=pa.list_(pa.int32()))}),
        numPartitions=2)
    scorer = DeepSequenceScorer(
        inputCol="tokens", modelName="TestShortConvMoE",
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
    assert len(chosen) == 3                 # one dense layer, three expert
    counts = np.stack([[np.bincount(row.ravel(), minlength=16)
                        for row in layer] for layer in chosen], 1)
    assert np.array_equal(
        np.asarray([r["experts"] for r in rows]).reshape(7, 3, 16), counts)


def test_short_convolution_alone_matches_reference_and_is_causal(key):
    s = sizes()
    p = ref.init_layer(key, s, 0, True)["conv"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, WINDOW, s.hidden))
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.short_conv(p, row, identity) for row in x])
        got = shortconv_moe.short_conv(p, x)
        t = 9
        moved = shortconv_moe.short_conv(p, x.at[0, t].add(1.0))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # the output projection mixes channels and no positions: a changed token
    # moves nothing before it, and (through the three taps) t … t + 2 alone
    np.testing.assert_allclose(moved[0, :t], got[0, :t], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(moved[0, t + 3:], got[0, t + 3:], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(moved[1], got[1], rtol=1e-5, atol=1e-6)
    assert all(np.abs(moved[0, t + j] - got[0, t + j]).max() > 1e-3
               for j in range(3))
    # ... and the taps alone, of the reference: exactly those positions of c
    z = jnp.zeros((WINDOW, s.hidden)).at[t].set(1.0)
    c = ref.conv_taps(z, p["taps"])
    assert np.array_equal(np.flatnonzero(np.abs(c).sum(1)), [t, t + 1, t + 2])
    np.testing.assert_array_equal(c[t:t + 3], p["taps"].T[::-1])


def test_grouped_attention_alone_matches_reference(key):
    s = sizes()
    p = ref.init_layer(key, s, 1, False)["attn"]
    assert p["k"].shape == (s.hidden, 2 * 8) and p["q_norm"].shape == (8,)
    x = jax.random.normal(jax.random.PRNGKey(6), (WINDOW, s.hidden))
    with jax.default_matmul_precision("highest"):
        want = ref.attention(p, x, s, identity, block=5)
        got, fused, scored = shortconv_moe.grouped_attention(p, x, MODEL)
        moved, _, _ = shortconv_moe.grouped_attention(p, x.at[-1].add(1.0),
                                                      MODEL)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # a head width of 8 on a CPU: the blocked path, blocks of 8 queries each
    # against its causal prefix
    assert fused == 0 and scored == 8 * (8 + 16 + 24)
    # causal: a later token does not move an earlier one
    np.testing.assert_allclose(moved[:-1], got[:-1], rtol=1e-5, atol=1e-6)


def test_blocked_attention_reads_grouped_keys_without_copies():
    """8 query heads on 2 key heads: the same as the keys repeated."""
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (h, WINDOW, 8))
               for i, h in enumerate((8, 2, 2)))
    with jax.default_matmul_precision("highest"):
        grouped = latent_moe._blocked_attention(q, k, v, 7)
        repeated = latent_moe._blocked_attention(
            q, jnp.repeat(k, 4, 0), jnp.repeat(v, 4, 0), 7)
    np.testing.assert_allclose(grouped, repeated, rtol=1e-5, atol=1e-6)


# -- the fused kernel at a head width of 64: a key head's query heads stacked --

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


def exact_attention(q, k, v, heads, span):
    """A float32 soft-max at full precision over the operands as the paths
    round them, keys repeated to the query heads, the band as a mask."""
    window = q.shape[0]
    q, k, v = (jnp.swapaxes(a.astype(jnp.bfloat16).astype(
        jnp.float32).reshape(window, -1, NARROW), 0, 1) for a in (q, k, v))
    k, v = (jnp.repeat(a, heads // k.shape[0], 0) for a in (k, v))
    at = jnp.arange(window)
    seen = at[None, :] <= at[:, None]
    if span is not None:
        seen &= at[None, :] > at[:, None] - span
    with jax.default_matmul_precision("highest"):
        scores = jnp.where(seen, jnp.einsum("hqd,hkd->hqk", q, k), -jnp.inf)
        out = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1), v)
    return jnp.swapaxes(out, 0, 1).reshape(window, -1)


def distance(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


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
    fused = latent_moe.fused_causal_attention(
        q.astype(v.dtype), None, k.astype(v.dtype), None, v, heads=heads,
        span=span, query_tile=256, key_tile=128, interpret=True)
    blocked, engaged, scored = latent_moe.grouped_causal_attention(
        q, k, v, heads, 64, span)
    assert engaged == 0 and fused.dtype == blocked.dtype == jnp.bfloat16
    assert fused.shape == blocked.shape == (window, heads * NARROW)
    assert scored == latent_moe.scored_keys(window, span, 64)
    exact = exact_attention(q, k, v, heads, span)
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
    run = functools.partial(latent_moe.fused_causal_attention, heads=heads,
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


def test_attention_layer_of_an_unnamed_kind_tells_what_was_lowered(
        monkeypatch):
    """A stack that names no kinds of layer (LFM2's): with the TPU branch
    taken at a head width of 64 (the kernel interpreted at small tiles) its
    attention layer tells ``fused`` 1 for each window and no scored keys, and
    the block's output is the blocked path's."""
    window, hidden = 256, 256
    c = dataclasses.replace(MODEL, hidden=hidden, heads=4, kv_heads=2,
                            head_dim=NARROW, dense_width=128)
    keys = jax.random.split(jax.random.PRNGKey(5), 8)

    def weight(key, rows, columns):
        return (jax.random.normal(key, (rows, columns)) * rows ** -0.5
                ).astype(jnp.bfloat16)

    layer = {"operator_norm": jnp.ones((hidden,)), "ffn_norm": jnp.ones(
        (hidden,)), "attn": {
            "q": weight(keys[0], hidden, 4 * NARROW),
            "k": weight(keys[1], hidden, 2 * NARROW),
            "v": weight(keys[2], hidden, 2 * NARROW),
            "out": weight(keys[3], 4 * NARROW, hidden),
            "q_norm": jnp.ones((NARROW,)), "k_norm": jnp.ones((NARROW,))},
        "mlp": {"gate": weight(keys[4], hidden, 128),
                "up": weight(keys[5], hidden, 128),
                "down": weight(keys[6], 128, hidden)}}
    h = jax.random.normal(keys[7], (2, window, hidden))
    block = shortconv_moe.block.__wrapped__
    want, _, told = jax.jit(lambda layer, h: block(layer, h, c))(layer, h)
    assert told["fused"].tolist() == [0, 0] and set(told) == {"fused"}
    monkeypatch.setattr(latent_moe, "FUSED_NARROW_QUERY_TILE", 128)
    monkeypatch.setattr(latent_moe, "FUSED_NARROW_KEY_TILE", 128)
    monkeypatch.setattr(
        latent_moe, "fused_causal_attention", functools.partial(
            latent_moe.fused_causal_attention, interpret=True))
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *operands, tpu, default: tpu(*operands))
    got, _, told = jax.jit(lambda layer, h: block(layer, h, c))(layer, h)
    assert told["fused"].tolist() == [1, 1] and set(told) == {"fused"}
    assert distance(got, want) < 0.002


def test_selection_bias_turns_a_choice_and_leaves_the_weights_unbiased(key):
    s = sizes()
    layer = ref.init_layer(key, s, 2, False)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(7), (40, s.hidden))
    with jax.default_matmul_precision("highest"):
        chosen, weights = latent_moe.route(layer, x, MODEL)
        ref_chosen, ref_weights = ref.route(layer, x, s)
        unbiased, _ = latent_moe.route(
            {"router": layer["router"]}, x, MODEL)
        scores = jax.nn.sigmoid(x @ layer["router"])
    assert np.array_equal(chosen, ref_chosen)
    np.testing.assert_allclose(weights, ref_weights, rtol=1e-5)
    # the drawn bias (N(0, 0.05)) turns some of the 40 tokens' choices
    turned = np.any(np.sort(chosen, -1) != np.sort(unbiased, -1), -1)
    assert 0 < turned.sum() < 40
    # the weights are the chosen experts' own sigmoids over their sum + 1e-6
    own = np.take_along_axis(np.asarray(scores), np.asarray(chosen), -1)
    np.testing.assert_allclose(
        weights, own / (own.sum(-1, keepdims=True) + 1e-6), rtol=1e-5)
    # a bias that lifts one expert over all others puts it in every choice
    # and moves no weight of the others' ratio
    lifted = dict(layer, expert_bias=layer["expert_bias"].at[3].add(10.0))
    chosen, weights = latent_moe.route(lifted, x, MODEL)
    assert (chosen == 3).any(-1).all()
    assert np.all(weights <= 1.0) and np.all(weights > 0.0)


def test_shares_add_up_to_the_uncut_layer(key):
    """32 experts as 0–7, 8–15, 16–23, 24–31: the routed parts of the four
    shares (there is no shared expert) are the uncut reference's layer, and
    the layer held whole gives it in one piece."""
    everything = tuple(range(32))
    s = sizes(num_experts=32, experts_held=list(everything))
    model = dataclasses.replace(MODEL, experts=32)
    x = jax.random.normal(jax.random.PRNGKey(8), (40, s.hidden))
    with jax.default_matmul_precision("highest"):
        whole = ref.init_layer(key, s, 2, False)["moe"]
        want, _ = ref.expert_layer(whole, x, s, identity)
        total, pairs = 0.0, 0
        for share in (everything[i:i + 8] for i in range(0, 32, 8)):
            layer = ref.init_layer(key, s, 2, False, experts_held=share)
            # an expert's weights are its own, whichever share holds it
            np.testing.assert_array_equal(
                layer["moe"]["experts"]["up"],
                whole["experts"]["up"][share[0]:share[0] + 8])
            config = dataclasses.replace(model, experts_held=share)
            part, _, counts, _, _ = latent_moe.routed_experts(layer["moe"], x,
                                                           config)
            ref_part, _ = ref.routed_part(layer["moe"], x, s, identity,
                                          experts_held=share)
            np.testing.assert_allclose(part, ref_part, rtol=1e-4, atol=1e-5)
            total = total + part
            pairs += int(counts.sum())
        uncut, _, counts, overflow, _ = latent_moe.routed_experts(
            whole, x, dataclasses.replace(model, experts_held=everything))
    assert pairs == 40 * 4          # every (token, expert) pair, exactly once
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(uncut, want, rtol=1e-4, atol=1e-5)
    assert int(counts.sum()) == 40 * 4 and not overflow.any()


def test_no_pair_is_dropped_and_the_buffer_is_the_pairs_when_all_are_held(
        key):
    """One expert gets every token: a layer held whole has a row for every
    pair, runs one round, and reports a buffer of exactly the pairs."""
    s = sizes()
    layer = ref.init_layer(key, s, 2, False)["moe"]
    layer["router"] = layer["router"].at[:, 5].add(4.0 / s.hidden ** 0.5)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (96, s.hidden)))
    assert latent_moe.buffer_capacity(96, MODEL) == 96 * 4
    with jax.default_matmul_precision("highest"):
        want, _ = ref.routed_part(layer, x, s, identity)
        got, chosen, counts, overflow, fused = jax.jit(
            lambda p, x: latent_moe.routed_experts(p, x, MODEL))(layer, x)
    assert int(counts[5]) == 96     # every token chose expert 5
    assert int(counts.sum()) == 96 * 4 and not overflow.any()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    stats = latent_moe.expert_stats(chosen, counts, overflow, fused, 4, MODEL)
    assert stats["buffer_rows"].tolist() == [96] * 4
    assert stats["local_pairs"].tolist() == [96] * 4
    assert float(stats["load_max_over_mean"]) == 96 / (96 * 4 / 16)
    # a share of the experts under the same router: further rounds, each
    # reported whole, their rows shared out among the launch's windows
    config = dataclasses.replace(MODEL, experts_held=(4, 5, 6, 7),
                                 capacity_factor=1.0)
    part = ref.init_layer(key, s, 2, False, experts_held=(4, 5, 6, 7))["moe"]
    part["router"] = layer["router"]
    _, *told = latent_moe.routed_experts(part, x, config)
    counts = told[1]
    stats = latent_moe.expert_stats(*told, 4, config)
    rounds = -(-int(counts.sum()) // 96)
    assert rounds > 1 and int(stats["buffer_rows"].sum()) == rounds * 96
    assert int(stats["overflow_pairs"].sum()) == int(counts.sum()) - 96


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
    visits = latent_moe._group_visits(group_sizes, rows, tile)
    run = functools.partial(latent_moe.grouped_product, row_tile=tile,
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


@pytest.mark.parametrize("case", ["a-tail-of-no-group", "further-rounds",
                                  "held-whole"])
def test_routed_experts_through_the_kernel_equals_the_default_path(
        case, monkeypatch):
    """``routed_experts`` with the TPU branch taken (the kernel interpreted,
    a row tile of 16) against the ``lax.ragged_dot`` branch: a buffer whose
    tail belongs to no expert, whose rows the combine multiplies by nought —
    finite all the same, because the kernel zeroes them; the group sizes
    traced inside the rounds' ``fori_loop``; and a layer held whole."""
    held = tuple(range(16)) if case == "held-whole" else (4, 5, 6, 7)
    config = dataclasses.replace(
        MODEL, hidden=HIDDEN, expert_width=WIDTH, experts_held=held,
        capacity_factor=0.5 if case == "further-rounds" else 2.0)
    x, experts = expert_operands(len(held), 96, seed=3)
    layer = {"experts": experts, "router": jax.random.normal(
        jax.random.PRNGKey(4), (HIDDEN, 16), jnp.float32)}
    x = x.astype(jnp.float32)
    want, _, counts, overflow, fused = jax.jit(
        lambda p, x: latent_moe.routed_experts(p, x, config))(layer, x)
    assert fused == 0
    capacity = latent_moe.buffer_capacity(96, config)
    assert capacity % 16 == 0
    assert {"a-tail-of-no-group": int(counts.sum()) < capacity - 16,
            "further-rounds": capacity < int(counts.sum()),
            "held-whole": capacity == int(counts.sum()) == 96 * 4}[case]
    monkeypatch.setattr(latent_moe, "GROUPED_ROW_TILE", 16)
    monkeypatch.setattr(latent_moe, "grouped_product", functools.partial(
        latent_moe.grouped_product, row_tile=16, interpret=True))
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *operands, tpu, default: tpu(*operands))
    got, _, again, _, fused = jax.jit(
        lambda p, x: latent_moe.routed_experts(p, x, config))(layer, x)
    assert fused == 1 and np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(again, counts)
    # the two paths sum in another order, so a few of hidden's bfloat16
    # roundings fall the other way: a last place of one term of a row's sum
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("held,factor", [(16, 2.0), (16, 0.25), (4, 1.0),
                                         (4, 0.25)])
def test_the_combine_gathers_every_round_back_to_its_tokens(key, held,
                                                            factor):
    """The inverse permutation's gathers give the reference's sum in one
    round (a layer held whole) and in several (a short buffer), and every
    pair that meets a held expert is computed exactly once."""
    s = sizes()
    share = tuple(range(4, 4 + held)) if held < 16 else tuple(range(16))
    layer = ref.init_layer(key, s, 2, False, experts_held=share)["moe"]
    config = dataclasses.replace(MODEL, experts_held=share,
                                 capacity_factor=factor)
    x = jax.random.normal(jax.random.PRNGKey(10), (48, s.hidden))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.routed_part(layer, x, s, identity, experts_held=share)
        got, chosen, counts, overflow, _ = latent_moe.routed_experts(layer, x,
                                                                  config)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    capacity = latent_moe.buffer_capacity(48, config)
    # factor × the even share, in whole eights, and never more than the pairs
    assert capacity == {(16, 2.0): 192, (16, 0.25): 48, (4, 1.0): 48,
                        (4, 0.25): 16}[held, factor]
    assert int(counts.sum()) == int(np.isin(chosen, share).sum())
    assert int(overflow.sum()) == max(0, int(counts.sum()) - capacity)


def test_program_counts_reach_telemetry_and_not_the_caller(key):
    s = sizes()
    model = registry.build_sequence_scorer(
        "TestShortConvMoE", make_variables(key, s), WINDOW)
    from sparkdl_tpu.core import executor

    tokens = tokens_of(2, 3)
    with telemetry.Telemetry(name="t", out_dir="") as scope:
        out = executor.execute(model, tokens, batch_size=2)
        snapshot = scope.metrics.snapshot()
    assert set(out) == {"pooled", "logprobs", "expert_counts"}
    counters = snapshot["counters"]
    assert counters[telemetry.M_SEQUENCE_TOKENS] == 3 * WINDOW
    assert counters[telemetry.M_SEQUENCE_CONV_LAYERS] == 3 * 3
    assert counters[telemetry.M_SEQUENCE_FUSED_ATTENTION_LAYERS] == 0
    assert counters[telemetry.M_SEQUENCE_FUSED_HEAD_WINDOWS] == 0
    assert counters[telemetry.M_MOE_ROUTED_TOKENS] == 3 * WINDOW * 3
    # every expert is held: each token's four pairs stay, in each of the
    # three expert layers, and the buffers held exactly those
    assert counters[telemetry.M_MOE_LOCAL_PAIRS] == 3 * WINDOW * 3 * 4
    assert counters[telemetry.M_MOE_BUFFER_ROWS] == 3 * WINDOW * 3 * 4
    # a CPU, and widths under a lane group: three lax.ragged_dot a layer
    assert counters[telemetry.M_MOE_FUSED_PRODUCT_LAYERS] == 0
    assert counters[telemetry.M_MOE_OVERFLOW_PAIRS] == 0
    ratio = snapshot["histograms"][telemetry.M_MOE_LOAD_MAX_OVER_MEAN]
    assert ratio["count"] == 3 * 3 and ratio["min"] >= 1.0


def test_latent_model_reports_its_buffers_rows(key):
    """The other sequence model, a quarter of its experts held: the rows its
    grouped products ran are whole rounds of its buffer."""
    config = json.load(open(os.path.join(
        BENCH, "tests", "rehearsal", "configs", "testmoe-windows.json")))
    pangu = _load("references/openpangu_moe.py")
    s = pangu.sizes(config)
    variables = {**pangu.init_embed(key, s), **pangu.init_head(key, s),
                 "layers": [pangu.init_layer(key, s, i, i < s.dense_layers)
                            for i in range(s.layers)]}
    model = registry.build_sequence_scorer(
        "TestLatentMoE", variables, WINDOW,
        experts_held=config["experts_held"])
    from sparkdl_tpu.core import executor

    with telemetry.Telemetry(name="t", out_dir="") as scope:
        executor.execute(model, tokens_of(3, 4), batch_size=2)
        counters = scope.metrics.snapshot()["counters"]
    capacity = latent_moe.buffer_capacity(2 * WINDOW, dataclasses.replace(
        registry.SEQUENCE_MODELS["TestLatentMoE"],
        experts_held=tuple(config["experts_held"])))
    assert capacity == 2 * 2 * WINDOW * 4 * 4 // 16
    assert counters[telemetry.M_MOE_BUFFER_ROWS] % capacity == 0
    assert counters[telemetry.M_MOE_BUFFER_ROWS] >= 2 * capacity
    assert counters[telemetry.M_MOE_LOCAL_PAIRS] \
        <= counters[telemetry.M_MOE_BUFFER_ROWS]
    assert telemetry.M_SEQUENCE_CONV_LAYERS not in counters


def test_bfloat16_weights_are_taken_as_they_are_and_the_head_is_tied(key):
    s = sizes()
    variables = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                             make_variables(key, s))
    variables["head"] = variables["embed"]
    model = registry.build_sequence_scorer("TestShortConvMoE", variables,
                                           WINDOW)
    cast = model.with_dtype("bfloat16")
    assert cast.variables is variables          # no second copy
    assert cast.variables["head"] is cast.variables["embed"]
    tokens = tokens_of(3, 2)
    out = cast.apply_batch(tokens, batch_size=2)
    with jax.default_matmul_precision("highest"):
        pooled, logprobs, _ = ref.forward(key, s, tokens)
    assert out["pooled"].dtype == np.float32
    assert out["expert_counts"].dtype == np.int32   # counts are not cast
    assert np.abs(out["logprobs"] - logprobs).max() < 0.15
    assert np.abs(out["pooled"] - pooled).max() < 0.1


def test_the_stack_counts_the_windows_its_tied_head_fused(key, monkeypatch):
    """The pre-norm stack's forward with a tied head of 128 rows in
    bfloat16: the count reads 0 a window on the path that writes the logits
    and 1 with the head's TPU branch taken (the kernel interpreted: blocks
    of 8 positions, one tile; a window of 24 gives attention and the
    experts' products no tiles, so they lower what they lowered), and the
    log-probabilities agree. The head is the embedding itself, as it lies."""
    s = sizes(vocab_size=128)
    variables = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                             make_variables(key, s))
    variables["head"] = variables["embed"]
    config = dataclasses.replace(MODEL, vocab=128)
    tokens = tokens_of(9, 2, vocab=128)

    def run():
        out = jax.jit(lambda p, t: shortconv_moe.forward(p, t, config))(
            variables, tokens)
        return out, out[telemetry.PROGRAM_COUNTS][
            telemetry.M_SEQUENCE_FUSED_HEAD_WINDOWS].tolist()

    want, count = run()
    assert count == [0, 0]
    monkeypatch.setattr(latent_moe, "_head_blocks", lambda *shapes: (8, 128))
    monkeypatch.setattr(latent_moe, "fused_scoring_head", functools.partial(
        latent_moe.fused_scoring_head, interpret=True))
    monkeypatch.setattr(lax, "platform_dependent",
                        lambda *operands, tpu, default: tpu(*operands))
    got, count = run()
    assert count == [1, 1]
    assert got[telemetry.PROGRAM_COUNTS][
        telemetry.M_SEQUENCE_FUSED_ATTENTION_LAYERS].tolist() == [0, 0]
    np.testing.assert_allclose(got["logprobs"], want["logprobs"], rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_array_equal(got["pooled"], want["pooled"])


def test_an_id_outside_the_vocabulary_gives_no_number(key):
    s = sizes()
    model = registry.build_sequence_scorer(
        "TestShortConvMoE", make_variables(key, s), WINDOW)
    tokens = tokens_of(4, 3)
    tokens[1, 5] = 32                       # the embedding holds ids 0..31
    out = model.apply_batch(tokens, batch_size=4)
    assert np.isnan(out["pooled"][1]).all()
    assert np.isnan(out["logprobs"][1]).all()
    assert np.isfinite(out["pooled"][[0, 2]]).all()
    assert np.isfinite(out["logprobs"][[0, 2]]).all()


def test_builder_reads_the_kinds_and_the_share_off_the_weights(key):
    s = sizes()
    variables = make_variables(key, s)
    model = registry.build_sequence_scorer("TestShortConvMoE", variables,
                                           WINDOW)
    assert model.input_spec.shape == (None, WINDOW)
    assert model.input_spec.dtype == "int32"
    # a config of one's own, of either type, is taken by its type
    own = registry.build_sequence_scorer(
        dataclasses.replace(MODEL, query_block=5), variables, WINDOW)
    tokens = tokens_of(5, 2)
    np.testing.assert_allclose(
        own.apply_batch(tokens, batch_size=2)["pooled"],
        model.apply_batch(tokens, batch_size=2)["pooled"], rtol=1e-4,
        atol=1e-5)
    with pytest.raises(ValueError, match="experts_held names 4"):
        registry.build_sequence_scorer("TestShortConvMoE", variables, WINDOW,
                                       experts_held=(0, 1, 2, 3))
    with pytest.raises(ValueError, match="Unsupported sequence model"):
        registry.build_sequence_scorer(object(), variables, WINDOW)
    mixerless = dict(variables, layers=[
        {k: v for k, v in layer.items() if k != "conv"}
        for layer in variables["layers"]])
    with pytest.raises(ValueError, match='"conv" or "attn"'):
        registry.build_sequence_scorer("TestShortConvMoE", mixerless, WINDOW)
    # the latent-attention model's weights do not pass for this one's
    with pytest.raises(ValueError, match='"conv" or "attn"'):
        registry.build_sequence_scorer(
            "TestShortConvMoE", dict(variables, layers=[
                dict(layer, conv=1, attn=1)
                for layer in variables["layers"]]), WINDOW)


@pytest.mark.parametrize("window", [16, 32])
def test_flops_shortconv_against_a_hand_count(window):
    flops = _load("flops_shortconv.py")
    pairs = window * (window + 1) // 2
    conv = window * (64 * 192 + 64 * 64 + 64 * 3)
    attention = window * (64 * 64 + 2 * 64 * 16 + 64 * 64) \
        + 8 * pairs * (8 + 8)
    expert = 3 * 64 * 32
    moe = window * (64 * 16 + 4 * expert)
    dense = window * 3 * 64 * 128
    head = (window - 1) * 64 * 32
    assert flops.window_flops(CONFIG, window) == 2 * (
        3 * conv + attention + dense + 3 * moe + head)


def test_flops_shortconv_at_the_published_widths():
    """ISSUE 37's own arithmetic: 7.63 TFLOP a window of 4,096, the routed
    experts 56.7 % of it."""
    flops = _load("flops_shortconv.py")
    config = json.load(open(os.path.join(BENCH, "configs",
                                         "lfm2-8b-a1b.json")))
    macs = flops.macs_per_window(config, 4096)
    total = 2 * sum(macs.values())
    assert abs(total / 7.63e12 - 1) < 0.002
    share = {k: 2 * v / total for k, v in macs.items()}
    assert abs(share["routed_experts"] - 0.567) < 0.002
    assert abs(share["conv_projections"] - 0.180) < 0.002
    assert abs(share["head"] - 0.144) < 0.002
    assert abs(share["attention_scores_values"] - 0.027) < 0.002
