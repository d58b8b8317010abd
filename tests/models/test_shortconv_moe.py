"""The short-convolution sparse-expert scorer (models/shortconv_moe.py) and
the expert layer it shares with the latent-attention one, against the
benchmark's plain reference (benchmarks/references/lfm2_moe.py, which imports
nothing of the program) at small sizes on the CPU: through the transformer and
collect(); the short convolution alone, and that it is causal; grouped
attention with per-head norms alone, and what an attention layer tells of
the kernel; the selection bias; the share test; no pair dropped and a buffer
of exactly the pairs when all are held; the combine over one round and
several; the program's counts in telemetry; the benchmark's FLOP count by
hand. The kernels' own tests are in test_attention.py and test_experts.py."""

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
from sparkdl_tpu.models import (attention, experts, registry, scoring_head,
                                shortconv_moe)

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


# -- an attention layer at a head width of 64 ---------------------------------

NARROW = 64


def distance(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.linalg.norm(got - want) / np.linalg.norm(want)


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
    monkeypatch.setattr(attention, "FUSED_NARROW_QUERY_TILE", 128)
    monkeypatch.setattr(attention, "FUSED_NARROW_KEY_TILE", 128)
    monkeypatch.setattr(
        attention, "fused_causal_attention", functools.partial(
            attention.fused_causal_attention, interpret=True))
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
        chosen, weights = experts.route(layer, x, MODEL.moe)
        ref_chosen, ref_weights = ref.route(layer, x, s)
        unbiased, _ = experts.route(
            {"router": layer["router"]}, x, MODEL.moe)
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
    chosen, weights = experts.route(lifted, x, MODEL.moe)
    assert (chosen == 3).any(-1).all()
    assert np.all(weights <= 1.0) and np.all(weights > 0.0)


def test_shares_add_up_to_the_uncut_layer(key):
    """32 experts as 0–7, 8–15, 16–23, 24–31: the routed parts of the four
    shares (there is no shared expert) are the uncut reference's layer, and
    the layer held whole gives it in one piece."""
    everything = tuple(range(32))
    s = sizes(num_experts=32, experts_held=list(everything))
    model = dataclasses.replace(MODEL.moe, experts=32)
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
            part, told = experts.routed_experts(layer["moe"], x, config)
            ref_part, _ = ref.routed_part(layer["moe"], x, s, identity,
                                          experts_held=share)
            np.testing.assert_allclose(part, ref_part, rtol=1e-4, atol=1e-5)
            total = total + part
            pairs += int(told["counts"].sum())
        uncut, told = experts.routed_experts(
            whole, x, dataclasses.replace(model, experts_held=everything))
    assert pairs == 40 * 4          # every (token, expert) pair, exactly once
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(uncut, want, rtol=1e-4, atol=1e-5)
    assert int(told["counts"].sum()) == 40 * 4
    assert not told["overflow"].any()


def test_no_pair_is_dropped_and_the_buffer_is_the_pairs_when_all_are_held(
        key):
    """One expert gets every token: a layer held whole has a row for every
    pair, runs one round, and reports a buffer of exactly the pairs."""
    s = sizes()
    layer = ref.init_layer(key, s, 2, False)["moe"]
    layer["router"] = layer["router"].at[:, 5].add(4.0 / s.hidden ** 0.5)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (96, s.hidden)))
    assert experts.buffer_capacity(96, MODEL.moe) == 96 * 4
    with jax.default_matmul_precision("highest"):
        want, _ = ref.routed_part(layer, x, s, identity)
        got, told = jax.jit(
            lambda p, x: experts.routed_experts(p, x, MODEL.moe))(layer, x)
    counts = told["counts"]
    assert int(counts[5]) == 96     # every token chose expert 5
    assert int(counts.sum()) == 96 * 4 and not told["overflow"].any()
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    stats = experts.expert_stats(told, 4, MODEL.moe)
    assert stats["buffer_rows"].tolist() == [96] * 4
    assert stats["local_pairs"].tolist() == [96] * 4
    assert float(stats["load_max_over_mean"]) == 96 / (96 * 4 / 16)
    # a share of the experts under the same router: further rounds, each
    # reported whole, their rows shared out among the launch's windows
    config = dataclasses.replace(MODEL.moe, experts_held=(4, 5, 6, 7),
                                 capacity_factor=1.0)
    part = ref.init_layer(key, s, 2, False, experts_held=(4, 5, 6, 7))["moe"]
    part["router"] = layer["router"]
    _, told = experts.routed_experts(part, x, config)
    counts = told["counts"]
    stats = experts.expert_stats(told, 4, config)
    rounds = -(-int(counts.sum()) // 96)
    assert rounds > 1 and int(stats["buffer_rows"].sum()) == rounds * 96
    assert int(stats["overflow_pairs"].sum()) == int(counts.sum()) - 96


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
    config = dataclasses.replace(MODEL.moe, experts_held=share,
                                 capacity_factor=factor)
    x = jax.random.normal(jax.random.PRNGKey(10), (48, s.hidden))
    with jax.default_matmul_precision("highest"):
        want, _ = ref.routed_part(layer, x, s, identity, experts_held=share)
        got, told = experts.routed_experts(layer, x, config)
    chosen, counts, overflow = (told[name] for name in (
        "chosen", "counts", "overflow"))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    capacity = experts.buffer_capacity(48, config)
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
    assert counters[telemetry.M_MOE_FUSED_COMBINE_LAYERS] == 0
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
    capacity = experts.buffer_capacity(2 * WINDOW, dataclasses.replace(
        registry.SEQUENCE_MODELS["TestLatentMoE"].moe,
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
    monkeypatch.setattr(scoring_head, "_head_blocks",
                        lambda *shapes: (8, 128))
    monkeypatch.setattr(scoring_head, "fused_scoring_head", functools.partial(
        scoring_head.fused_scoring_head, interpret=True))
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
