"""The pre-norm stack whose attention layers differ by kind (models/
shortconv_moe.py with ``layer_types``: sliding-window layers among full ones,
each kind with its own rotary, a soft-max router) against the benchmark's
plain reference (benchmarks/references/mellum2_moe.py, which imports nothing
of the program) at the ``TestSpanMoE`` sizes on the CPU: through the
transformer and collect(); each kind of attention alone, and that a sliding
layer reads its span and no further; YaRN's ramp, frequencies and amplitude
against a hand count; the soft-max router and the share test; the head in
blocks of positions; the counts in telemetry; the benchmark's FLOP count by
hand. The fused kernel with a span on grouped keys, the key tiles it visits
and which path a lowering takes are test_attention.py's."""

import dataclasses
import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
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


ref = _load("references/mellum2_moe.py")
CONFIG = json.load(open(os.path.join(
    BENCH, "tests", "rehearsal", "configs", "testspan-windows.json")))
PUBLISHED = json.load(open(os.path.join(BENCH, "configs",
                                        "mellum2-12b-instruct.json")))
MODEL = registry.SEQUENCE_MODELS["TestSpanMoE"]
WINDOW = 40                     # five spans of 8


def identity(a):
    return a


def sizes(**changes):
    return ref.sizes(dict(CONFIG, **changes))


def make_variables(key, s):
    return {**ref.init_embed(key, s), **ref.init_head(key, s),
            "layers": [ref.init_layer(key, s, i) for i in range(s.layers)]}


def tokens_of(seed, rows, vocab=32):
    return np.random.default_rng(seed).integers(
        0, vocab, size=(rows, WINDOW)).astype(np.int32)


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(17)


# -- the program against the reference ----------------------------------------


def test_scorer_matches_reference_through_transformer_and_collect(key):
    s = sizes()
    tokens = tokens_of(1, 5)
    frame = DataFrame.fromArrow(pa.table({
        "id": pa.array(np.arange(5)),
        "tokens": pa.array(list(tokens), type=pa.list_(pa.int32()))}),
        numPartitions=2)
    scorer = DeepSequenceScorer(
        inputCol="tokens", modelName="TestSpanMoE",
        weights=make_variables(key, s), window=WINDOW, batchSize=2,
        expertCountsCol="experts")
    rows = sorted(scorer.transform(frame).collect(), key=lambda r: r["id"])
    with jax.default_matmul_precision("highest"):
        pooled, logprobs, chosen = ref.forward(key, s, tokens)
    assert [r["tokens"] for r in rows] == tokens.tolist()
    np.testing.assert_allclose([r["pooled"] for r in rows], pooled,
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose([r["logprobs"] for r in rows], logprobs,
                               rtol=2e-4, atol=2e-5)
    assert all(r["logprobs"][-1] == 0.0 for r in rows)
    # three sliding layers, a full one, a sliding one: all five route
    assert len(chosen) == 5
    counts = np.stack([[np.bincount(row.ravel(), minlength=16)
                        for row in layer] for layer in chosen], 1)
    assert np.array_equal(
        np.asarray([r["experts"] for r in rows]).reshape(5, 5, 16), counts)


@pytest.mark.parametrize("mechanism", ["span", "yarn"])
def test_the_reference_without_a_mechanism_is_another_model(key, mechanism):
    """What the two controls of the cell rest on: leaving the span out of the
    sliding layers, or YaRN out of the full ones, moves the outputs by far
    more than any rounding."""
    s = sizes()
    tokens = tokens_of(2, 2)
    with jax.default_matmul_precision("highest"):
        pooled, logprobs, _ = ref.forward(key, s, tokens)
        other, other_logprobs, _ = ref.forward(
            key, ref.without(s, mechanism), tokens)
    assert np.abs(other - pooled).max() > 0.02
    assert np.abs(other_logprobs - logprobs).max() > 0.2
    # the first span's positions of a window read the same keys either way,
    # but a full layer's rotary reaches every position but the first
    if mechanism == "span":
        np.testing.assert_allclose(other_logprobs[:, :8], logprobs[:, :8],
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_attention_of_each_kind_alone_matches_reference(key, kind):
    s = sizes()
    p = ref.init_layer(key, s, 0)["attn"]
    assert p["k"].shape == (s.hidden, 2 * 8) and "q_norm" not in p
    x = jax.random.normal(jax.random.PRNGKey(6), (WINDOW, s.hidden))
    with jax.default_matmul_precision("highest"):
        want = ref.attention(p, x, s, kind, identity, block=5)
        got, fused, scored = shortconv_moe.grouped_attention(p, x, MODEL,
                                                             kind)
        t = 12
        moved, _, _ = shortconv_moe.grouped_attention(
            p, x.at[t].add(1.0), MODEL, kind)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    assert fused == 0               # a CPU, and a head width of 8
    # a changed token moves nothing before it; in a sliding layer it moves
    # the 8 positions whose span holds it and none after them
    np.testing.assert_allclose(moved[:t], got[:t], rtol=1e-5, atol=1e-6)
    changed = np.flatnonzero(np.abs(moved - got).max(1) > 1e-4)
    if kind == "sliding_attention":
        assert changed.tolist() == list(range(t, t + 8))
        assert scored == attention.scored_keys(WINDOW, 8, 8) == 8 * (
            8 + 4 * 15)
    else:
        assert changed.tolist() == list(range(t, WINDOW))
        assert scored == 8 * (8 + 16 + 24 + 32 + 40)


def test_per_head_norms_apply_where_the_weights_hold_them(key):
    """The config names no norm per head (`assumed`); weights that hold the
    gains get the norm, as the other family's layers do."""
    s = sizes()
    p = ref.init_layer(key, s, 1)["attn"]
    x = jax.random.normal(jax.random.PRNGKey(7), (WINDOW, s.hidden))
    ones = dict(p, q_norm=jnp.ones((8,)), k_norm=jnp.ones((8,)))
    with jax.default_matmul_precision("highest"):
        plain, _, _ = shortconv_moe.grouped_attention(p, x, MODEL,
                                                      "full_attention")
        normed, _, _ = shortconv_moe.grouped_attention(ones, x, MODEL,
                                                       "full_attention")
    assert np.abs(np.asarray(normed) - plain).max() > 1e-2


# -- the rotary of each kind --------------------------------------------------


def test_yarn_ramp_frequencies_and_amplitude_against_a_hand_count():
    """ISSUE 39's arithmetic at the published sizes, derived again: the ramp
    runs from dimension 18 to 35 of a head's 64, the frequencies below it are
    the plain ones, those above a sixteenth, and the amplitude is
    0.1 · ln 16 + 1."""
    rope = dict(registry.SEQUENCE_MODELS[
        "Mellum2-12B-A2.5B-Instruct"].rope)["full_attention"]
    theta, d = 500000.0, 128

    def dimension(turns):
        return d * math.log(8192 / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    assert 18 < dimension(32) < 19 and 34 < dimension(1) < 35
    assert rope.ramp(64) == (18, 35)
    omega = rope.frequencies(64)
    plain = theta ** (-np.arange(64) / 64)
    np.testing.assert_allclose(omega[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(omega[35:], plain[35:] / 16, rtol=1e-6)
    j = 26
    r = (j - 18) / (35 - 18)
    np.testing.assert_allclose(omega[j], plain[j] * ((1 - r) + r / 16),
                               rtol=1e-6)
    assert np.all(np.diff(omega) < 0)
    assert abs(rope.amplitude - (0.1 * math.log(16) + 1)) < 1e-12
    # the reference derives the same table from the config's own keys
    table, amplitude = ref.rope_table(
        PUBLISHED["rope_parameters"]["full_attention"], 128)
    np.testing.assert_allclose(table, omega, rtol=2e-6)
    assert amplitude == rope.amplitude
    # the sliding layers' entry is the plain rotary, which is the default
    table, amplitude = ref.rope_table(
        PUBLISHED["rope_parameters"]["sliding_attention"], 128)
    np.testing.assert_allclose(table, plain, rtol=2e-6)
    assert amplitude == 1.0
    assert attention.Rope(theta).frequencies(64).tolist() == plain.astype(
        np.float32).tolist()


def test_rotary_takes_frequencies_and_an_amplitude_and_defaults_to_plain():
    x = jax.random.normal(jax.random.PRNGKey(8), (WINDOW, 2 * 8))
    plain = attention.rotary(x, 100.0, 2)
    same = attention.rotary(x, 100.0, 2,
                            frequencies=attention.Rope(100.0).frequencies(4))
    np.testing.assert_allclose(same, plain, rtol=1e-5, atol=1e-6)
    rope = dict(MODEL.rope)["full_attention"]
    assert rope.ramp(4) == (0, 2)
    turned = attention.rotary(x, 100.0, 2, rope.frequencies(4),
                              rope.amplitude)
    want = ref.rotary(jnp.swapaxes(x.reshape(WINDOW, 2, 8), 0, 1),
                      *ref.rope_table(CONFIG["rope_parameters"][
                          "full_attention"], 8))
    np.testing.assert_allclose(
        turned, jnp.swapaxes(want, 0, 1).reshape(WINDOW, -1), rtol=1e-5,
        atol=1e-6)
    # position 0 is not turned: the amplitude alone
    np.testing.assert_allclose(turned[0], x[0] * rope.amplitude, rtol=1e-6)


# -- the router and the share --------------------------------------------------


def test_soft_max_router_matches_reference(key):
    s = sizes()
    layer = ref.init_layer(key, s, 2)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(9), (40, s.hidden))
    with jax.default_matmul_precision("highest"):
        chosen, weights = experts.route(layer, x, MODEL.moe)
        ref_chosen, ref_weights = ref.route(layer, x, s)
        scores = jax.nn.softmax(x @ layer["router"], -1)
    assert np.array_equal(chosen, ref_chosen)
    np.testing.assert_allclose(weights, ref_weights, rtol=1e-5)
    # the chosen experts' own shares of the soft-max over all 16, over their
    # sum: they add up to one, and no sigmoid's do
    own = np.take_along_axis(np.asarray(scores), np.asarray(chosen), -1)
    np.testing.assert_allclose(weights, own / own.sum(-1, keepdims=True),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-5)
    sigmoid, _ = experts.route(
        layer, x, dataclasses.replace(MODEL.moe, scoring="sigmoid"))
    # a monotone score chooses the same experts; the weights differ
    assert np.array_equal(np.sort(sigmoid, -1), np.sort(chosen, -1))


def test_shares_add_up_to_the_uncut_layer(key):
    """16 experts as 0–3, 4–7, 8–11, 12–15: the routed parts of the four
    shares (there is no shared expert) are the uncut reference's layer, and
    the layer held whole gives it in one piece."""
    everything = tuple(range(16))
    s = sizes()
    x = jax.random.normal(jax.random.PRNGKey(10), (40, s.hidden))
    with jax.default_matmul_precision("highest"):
        whole = ref.init_layer(key, s, 2)["moe"]
        want, _ = ref.expert_layer(whole, x, s, identity)
        total, pairs = 0.0, 0
        for share in (everything[i:i + 4] for i in range(0, 16, 4)):
            layer = ref.init_layer(key, s, 2, experts_held=share)["moe"]
            # an expert's weights are its own, whichever share holds it
            np.testing.assert_array_equal(
                layer["experts"]["up"],
                whole["experts"]["up"][share[0]:share[0] + 4])
            config = dataclasses.replace(MODEL.moe, experts_held=share)
            part, told = experts.routed_experts(layer, x, config)
            ref_part, _ = ref.routed_part(layer, x, s, identity,
                                          experts_held=share)
            np.testing.assert_allclose(part, ref_part, rtol=1e-4, atol=1e-5)
            total = total + part
            pairs += int(told["counts"].sum())
        uncut, told = experts.routed_experts(whole, x, MODEL.moe)
    assert pairs == 40 * 4          # every (token, expert) pair, exactly once
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(uncut, want, rtol=1e-4, atol=1e-5)
    assert int(told["counts"].sum()) == 40 * 4
    assert not told["overflow"].any()


# -- the head in blocks ---------------------------------------------------------


def test_head_in_blocks_of_positions_equals_the_head_whole(key, monkeypatch):
    s = sizes()
    params = {**ref.init_embed(key, s), **ref.init_head(key, s)}
    h = jax.random.normal(jax.random.PRNGKey(11), (2, WINDOW, s.hidden))
    tokens = tokens_of(3, 2)
    whole, fused = scoring_head.score_head(params, h, tokens, s.eps)
    assert fused.tolist() == [0, 0]
    # 40 positions × 32 rows × 4 bytes: a bound of a quarter makes 4 blocks
    monkeypatch.setattr(scoring_head, "HEAD_LOGITS_BYTES", WINDOW * 32)
    blocks, _ = scoring_head.score_head(params, h, tokens, s.eps)
    np.testing.assert_allclose(blocks["logprobs"], whole["logprobs"],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(blocks["pooled"], whole["pooled"])
    assert not np.asarray(blocks["logprobs"][:, -1]).any()
    want = ref.head_forward(params, h, tokens, s, block=10)
    np.testing.assert_allclose(blocks["logprobs"], want[1], rtol=1e-4,
                               atol=1e-5)


# -- counts, the builder, the FLOP count ---------------------------------------


def test_program_counts_reach_telemetry_and_not_the_caller(key):
    s = sizes()
    model = registry.build_sequence_scorer(
        "TestSpanMoE", make_variables(key, s), WINDOW)
    from sparkdl_tpu.core import executor

    tokens = tokens_of(4, 3)
    with telemetry.Telemetry(name="t", out_dir="") as scope:
        out = executor.execute(model, tokens, batch_size=2)
        counters = scope.metrics.snapshot()["counters"]
    assert set(out) == {"pooled", "logprobs", "expert_counts"}
    assert counters[telemetry.M_SEQUENCE_TOKENS] == 3 * WINDOW
    # layers 0–2 and 4 of the five held are sliding ones
    assert counters[telemetry.M_SEQUENCE_WINDOW_ATTENTION_LAYERS] == 3 * 4
    assert counters[telemetry.M_SEQUENCE_FUSED_ATTENTION_LAYERS] == 0
    assert counters[telemetry.M_SEQUENCE_FUSED_HEAD_WINDOWS] == 0
    assert counters[telemetry.M_SEQUENCE_CONV_LAYERS] == 0
    # the blocked path, blocks of 8 queries: a sliding layer scores its block
    # and the 7 keys before it, the full layer each block's whole prefix
    sliding = 8 * (8 + 4 * 15)
    full = 8 * (8 + 16 + 24 + 32 + 40)
    assert counters[telemetry.M_SEQUENCE_SCORED_KEYS] == 3 * (4 * sliding
                                                               + full)
    assert counters[telemetry.M_MOE_LOCAL_PAIRS] == 3 * WINDOW * 5 * 4
    assert counters[telemetry.M_MOE_BUFFER_ROWS] == 3 * WINDOW * 5 * 4
    assert counters[telemetry.M_MOE_OVERFLOW_PAIRS] == 0


def test_the_other_stacks_report_no_span_counts(key):
    """The short-convolution model names no kinds of attention: its program's
    counts hold no span counts — and the layers whose attention was lowered
    to the kernel, which every stack reports."""
    lfm2 = _load("references/lfm2_moe.py")
    config = json.load(open(os.path.join(
        BENCH, "tests", "rehearsal", "configs", "testshortconv-windows.json")))
    s = lfm2.sizes(config)
    embed = lfm2.init_embed(key, s)["embed"]
    variables = {"embed": embed, "head": embed,
                 "final_norm": lfm2.init_head(key, s)["final_norm"],
                 "layers": [lfm2.init_layer(key, s, i, i < s.dense_layers)
                            for i in range(s.layers)]}
    model = registry.build_sequence_scorer("TestShortConvMoE", variables,
                                           WINDOW)
    counts = model.apply_batch(tokens_of(5, 2), batch_size=2)[
        telemetry.PROGRAM_COUNTS]
    assert telemetry.M_SEQUENCE_SCORED_KEYS not in counts
    assert telemetry.M_SEQUENCE_WINDOW_ATTENTION_LAYERS not in counts
    assert counts[telemetry.M_SEQUENCE_CONV_LAYERS].tolist() == [3, 3]
    # what its attention layers lowered to it does tell: on a CPU, no kernel
    assert counts[telemetry.M_SEQUENCE_FUSED_ATTENTION_LAYERS].tolist() == [
        0, 0]


def test_builder_takes_the_kinds_by_position(key):
    s = sizes()
    variables = make_variables(key, s)
    tokens = tokens_of(6, 2)
    five = registry.build_sequence_scorer("TestSpanMoE", variables, WINDOW)
    # the held layers are the leading ones: four of them end with the full
    # layer, three are all sliding
    four = registry.build_sequence_scorer(
        "TestSpanMoE", dict(variables, layers=variables["layers"][:4]),
        WINDOW)
    three = registry.build_sequence_scorer(
        "TestSpanMoE", dict(variables, layers=variables["layers"][:3]),
        WINDOW)
    counts = [m.apply_batch(tokens, batch_size=2)[telemetry.PROGRAM_COUNTS][
        telemetry.M_SEQUENCE_WINDOW_ATTENTION_LAYERS].tolist()
        for m in (five, four, three)]
    assert counts == [[4, 4], [3, 3], [3, 3]]
    with pytest.raises(ValueError, match="of its 8 layers; the weights hold 10"):
        registry.build_sequence_scorer(
            "TestSpanMoE", dict(variables, layers=variables["layers"] * 2),
            WINDOW)
    with pytest.raises(ValueError, match="of its 8 layers; the weights hold 5"):
        registry.build_sequence_scorer(
            "TestSpanMoE", dict(variables, layers=[
                {"conv": 1, **{k: v for k, v in layer.items() if k != "attn"}}
                for layer in variables["layers"]]), WINDOW)
    published = registry.SEQUENCE_MODELS["Mellum2-12B-A2.5B-Instruct"]
    assert list(published.layer_types) == PUBLISHED["published"][
        "layer_types"]
    assert list(published.layer_types[:8]) == PUBLISHED["layer_types"]
    assert (published.span, published.moe.experts, published.moe.top_k,
            published.vocab, published.moe.scoring) == (1024, 64, 8, 98304,
                                                        "softmax")


@pytest.mark.parametrize("window", [16, 40])
def test_flops_window_against_a_hand_count(window):
    flops = _load("flops_window.py")
    full = window * (window + 1) // 2
    sliding = sum(min(t + 1, 8) for t in range(window))
    assert flops.attended_pairs(CONFIG, window, "sliding_attention") == sliding
    projections = window * (64 * 64 + 2 * 64 * 16 + 64 * 64)
    expert = 3 * 64 * 32
    moe = window * (64 * 16 + 4 * expert)
    head = (window - 1) * 64 * 32
    assert flops.window_flops(CONFIG, window) == 2 * (
        5 * (projections + moe) + 8 * 2 * 8 * (full + 4 * sliding) + head)
    assert flops.attention_kernel_flops(CONFIG, window, "full_attention") \
        == 2 * 8 * full * 2 * 8


def test_flops_window_at_the_published_widths():
    """ISSUE 39's own arithmetic: 32.0 TFLOP a window of 16,384 tokens, and
    the configuration's 3,794,966,784 parameters."""
    flops = _load("flops_window.py")
    assert flops.attended_pairs(PUBLISHED, 16384, "full_attention") \
        == 134225920
    assert flops.attended_pairs(PUBLISHED, 16384, "sliding_attention") \
        == 16253440
    macs = flops.macs_per_window(PUBLISHED, 16384)
    tera = {k: 2 * v / 1e12 for k, v in macs.items()}
    assert abs(sum(tera.values()) - 32.0) < 0.05
    for part, want in (("routed_experts", 12.99), ("head", 7.42),
                       ("attention_projections", 5.57),
                       ("full_attention_scores_values", 4.40),
                       ("sliding_attention_scores_values", 1.60),
                       ("router", 0.04)):
        assert abs(tera[part] - want) < 0.006, part
    # a program that masked the sliding layers and skipped nothing: 36 % more
    masked = sum(tera.values()) + 6 * (2.199 - 0.266)
    assert abs(masked / sum(tera.values()) - 1.36) < 0.005
    s = ref.sizes(PUBLISHED)
    layer = (2 * s.hidden * s.heads * s.head_dim
             + 2 * s.hidden * s.kv_heads * s.head_dim + 2 * s.hidden
             + s.hidden * s.experts + 64 * 3 * s.hidden * s.expert_width)
    assert layer == 417747456
    assert 8 * layer + 2 * s.vocab * s.hidden + s.hidden == 3794966784
    assert "deployment" in PUBLISHED and PUBLISHED["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types"]
