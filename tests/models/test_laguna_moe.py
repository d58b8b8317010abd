"""The pre-norm stack with what the ``laguna`` family adds (models/
shortconv_moe.py: query heads read off each layer's weights, a per-head
sigmoid gate on attention's output, a rotary that turns only part of a head,
a shared expert beside the routed ones, a dense leading layer) against the
benchmark's plain reference (benchmarks/references/laguna_moe.py, which
imports nothing of the program) at the ``TestLaguna`` sizes on the CPU:
through the transformer and collect(); each fault of the reference and the
float8 control put in the program's place; each kind of attention alone,
with the gate and without; the fused kernel, interpreted, against the
blocked path at 6 and 8 query heads a key head with the partial rotary
applied; the partial rotary and YaRN over the turned part against a hand
count; the sigmoid router with its scaling; the counts in telemetry; the
parameter counts; the benchmark's FLOP count by hand. The grouped kernel's
visits are test_experts.py's, and every stack's lowered program
test_program_identity.py's."""

import dataclasses
import functools
import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu.core import telemetry
from sparkdl_tpu.engine.dataframe import DataFrame
from sparkdl_tpu.ml import DeepSequenceScorer
from sparkdl_tpu.models import (attention, experts, registry, sequence_ops,
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


ref = _load("references/laguna_moe.py")
plain = _load("references/plain.py")


def _config(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = _config("tests", "rehearsal", "configs", "testlaguna-windows.json")
PUBLISHED = _config("configs", "laguna-xs2.json")
MODEL = registry.SEQUENCE_MODELS["TestLaguna"]
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


def gaps(got, want):
    """The largest |Δ| of the pooled states and of the log-probabilities."""
    return tuple(float(np.abs(np.asarray(g, np.float64) - w).max())
                 for g, w in zip(got, want))


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(17)


@pytest.fixture(scope="module")
def scored(key):
    """The program's rows through the transformer and collect(), and the
    sound reference's pass over the same windows."""
    s = sizes()
    tokens = tokens_of(1, 5)
    frame = DataFrame.fromArrow(pa.table({
        "id": pa.array(np.arange(5)),
        "tokens": pa.array(list(tokens), type=pa.list_(pa.int32()))}),
        numPartitions=2)
    scorer = DeepSequenceScorer(
        inputCol="tokens", modelName="TestLaguna",
        weights=make_variables(key, s), window=WINDOW, batchSize=2,
        expertCountsCol="experts")
    rows = sorted(scorer.transform(frame).collect(), key=lambda r: r["id"])
    with jax.default_matmul_precision("highest"):
        want = ref.forward(key, s, tokens)
    return tokens, rows, want


# -- the program against the reference ----------------------------------------

# float32 on both sides: the products' order is all that differs
TOLERANCE = 2e-4


def test_scorer_matches_reference_through_transformer_and_collect(scored):
    tokens, rows, (pooled, logprobs, chosen) = scored
    assert [r["tokens"] for r in rows] == tokens.tolist()
    got = ([r["pooled"] for r in rows], [r["logprobs"] for r in rows])
    assert max(gaps(got, (pooled, logprobs))) < TOLERANCE
    assert all(r["logprobs"][-1] == 0.0 for r in rows)
    # layer 0 is dense; the four after it route
    assert len(chosen) == 4
    counts = np.stack([[np.bincount(row.ravel(), minlength=16)
                        for row in layer] for layer in chosen], 1)
    assert np.array_equal(
        np.asarray([r["experts"] for r in rows]).reshape(5, 4, 16), counts)


@pytest.mark.parametrize("fault", ["shared", "gate", "rotary_part", "span",
                                   "float8", "rows_swapped"])
def test_each_fault_in_the_programs_place_fails_by_far(key, scored, fault):
    """What the cell's controls rest on: the reference without one of the
    model's mechanisms, with float8 operands, or the rows handed back in
    each other's place, reads many times the tolerance against the sound
    reference that the program meets."""
    tokens, rows, want = scored
    if fault == "rows_swapped":
        got = ([r["pooled"] for r in rows][::-1],
               [r["logprobs"] for r in rows][::-1])
    else:
        s = sizes()
        with jax.default_matmul_precision("highest"):
            got = ref.forward(
                key, s if fault == "float8" else ref.without(s, fault),
                tokens, plain.fp8_operands if fault == "float8" else None)
    pooled, logprobs = gaps(got, want[:2])
    assert pooled > 50 * TOLERANCE and logprobs > 250 * TOLERANCE


@pytest.mark.parametrize("kind,heads", [("full_attention", 6),
                                        ("sliding_attention", 8)])
def test_attention_reads_its_heads_off_the_weights_and_gates_where_held(
        key, kind, heads):
    s = sizes()
    index = 0 if kind == "full_attention" else 1
    p = ref.init_layer(key, s, index)["attn"]
    assert p["q"].shape == (64, heads * 16) and p["gate"].shape == (64, heads)
    x = jax.random.normal(jax.random.PRNGKey(6), (WINDOW, s.hidden))
    ungated = {k: v for k, v in p.items() if k != "gate"}
    with jax.default_matmul_precision("highest"):
        want = ref.attention(p, x, s, kind, identity, block=5)
        want_ungated = ref.attention(p, x, ref.without(s, "gate"), kind,
                                     identity, block=5)
        got, fused, _ = shortconv_moe.grouped_attention(p, x, MODEL, kind)
        plain_out, _, _ = shortconv_moe.grouped_attention(ungated, x, MODEL,
                                                          kind)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(plain_out, want_ungated, rtol=1e-4, atol=1e-5)
    assert np.abs(np.asarray(plain_out) - got).max() > 1e-2
    assert fused == 0               # a CPU, and a head width of 16


def test_the_builder_refuses_query_heads_that_no_key_head_divides(key):
    s = sizes()
    variables = make_variables(key, s)
    layers = [dict(layer) for layer in variables["layers"]]
    attn = dict(layers[1]["attn"])
    attn["q"] = attn["q"][:, :7 * 16]
    layers[1]["attn"] = attn
    with pytest.raises(ValueError, match="multiple of its key heads"):
        registry.build_sequence_scorer("TestLaguna",
                                       dict(variables, layers=layers), WINDOW)


# -- the partial rotary --------------------------------------------------------


def test_partial_rotary_turns_the_leading_share_and_passes_the_rest(key):
    """A full layer turns the first 8 of a head's 16 dimensions by YaRN over
    those 8 — the rest come out as they went in, at every position — and
    the reference's table, made from the config's own keys, is the
    program's."""
    rope = dict(MODEL.rope)["full_attention"]
    assert rope.turned(16) == 8 and rope.ramp(4) == (0, 2)
    table, amplitude = ref.rope_table(
        CONFIG["rope_parameters"]["full_attention"], 16)
    np.testing.assert_allclose(table, rope.frequencies(4), rtol=2e-6)
    assert amplitude == rope.amplitude
    s = sizes()
    p = ref.init_layer(key, s, 4)["attn"]
    x = jax.random.normal(jax.random.PRNGKey(7), (WINDOW, s.hidden))
    seen = {}

    def spy(q, k, v, heads, block, span=None):
        seen.update(q=q, k=k)
        return attention.grouped_causal_attention(q, k, v, heads, block,
                                                  span)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(shortconv_moe, "grouped_causal_attention", spy)
        shortconv_moe.grouped_attention(p, x, MODEL, "full_attention")
    k = np.asarray(seen["k"]).reshape(WINDOW, 2, 16)
    raw = np.asarray(x @ p["k"]).reshape(WINDOW, 2, 16)
    np.testing.assert_allclose(k[..., 8:], raw[..., 8:], rtol=1e-5,
                               atol=1e-6)
    assert np.abs(k[1:, :, :8] - raw[1:, :, :8]).max() > 1e-2
    want = ref.rotary(jnp.swapaxes(jnp.asarray(raw), 0, 1), table, amplitude)
    np.testing.assert_allclose(k, jnp.swapaxes(want, 0, 1), rtol=1e-5,
                               atol=1e-5)


def test_yarn_over_the_turned_half_at_the_published_sizes():
    """Laguna-XS.2's full layers: YaRN over 64 of 128 dimensions, 32
    frequencies, the ramp from 5 to 16 of them, the amplitude 0.1 · ln 64 +
    1; its sliding layers the plain rotary of θ 10,000 over all 128."""
    published = registry.SEQUENCE_MODELS["Laguna-XS.2"]
    rope = dict(published.rope)["full_attention"]
    theta, turned = 500000.0, 64

    def dimension(turns):
        return turned * math.log(4096 / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    assert 5 < dimension(64) < 6 and 15 < dimension(1) < 16
    assert rope.turned(128) == 64 and rope.ramp(32) == (5, 16)
    omega = rope.frequencies(32)
    base = theta ** (-np.arange(32) / 32)
    np.testing.assert_allclose(omega[:6], base[:6], rtol=1e-6)
    np.testing.assert_allclose(omega[16:], base[16:] / 64, rtol=1e-6)
    assert abs(rope.amplitude - (0.1 * math.log(64) + 1)) < 1e-12
    table, amplitude = ref.rope_table(
        PUBLISHED["rope_parameters"]["full_attention"], 128)
    np.testing.assert_allclose(table, omega, rtol=2e-6)
    assert amplitude == rope.amplitude
    sliding = dict(published.rope)["sliding_attention"]
    assert sliding.turned(128) == 128
    table, amplitude = ref.rope_table(
        PUBLISHED["rope_parameters"]["sliding_attention"], 128)
    np.testing.assert_allclose(table, sliding.frequencies(64), rtol=2e-6)
    assert amplitude == 1.0


# -- the fused kernel at the published group sizes ----------------------------


@pytest.mark.parametrize("kind,heads", [("full_attention", 6),
                                        ("sliding_attention", 8)])
def test_fused_kernel_matches_the_blocked_path_with_the_partial_rotary(
        kind, heads):
    """One key head of 128 and its 6 (a full layer's 48 / 8) or 8 (a
    sliding layer's 64 / 8) query heads, bfloat16 weights, the layer's
    rotary — half a head for the full kind — applied before either path:
    the interpreted kernel and the blocked path agree to bfloat16's
    rounding, with a span and without."""
    c = dataclasses.replace(
        registry.SEQUENCE_MODELS["Laguna-XS.2"], hidden=256, span=128)
    keys = jax.random.split(jax.random.PRNGKey(heads), 5)

    def weight(k, shape, scale=1.0):
        return (jax.random.normal(k, shape) * scale * shape[0] ** -0.5
                ).astype(jnp.bfloat16)

    p = {"q": weight(keys[0], (256, heads * 128), 2.0),
         "k": weight(keys[1], (256, 128), 2.0),
         "v": weight(keys[2], (256, 128)),
         "gate": weight(keys[3], (256, heads)),
         "out": weight(keys[4], (heads * 128, 256))}
    x = jax.random.normal(jax.random.PRNGKey(9), (512, 256))

    def interpreted(q, k, v, heads, block, span=None):
        out = attention.fused_causal_attention(
            q.astype(v.dtype), None, k.astype(v.dtype), None, v, heads=heads,
            span=span, query_tile=256, key_tile=128, interpret=True)
        return out, jnp.int32(1), jnp.int32(0)

    blocked, _, _ = shortconv_moe.grouped_attention(p, x, c, kind)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(shortconv_moe, "grouped_causal_attention", interpreted)
        fused, engaged, _ = shortconv_moe.grouped_attention(p, x, c, kind)
    assert engaged == 1
    blocked, fused = (np.asarray(a, np.float32) for a in (blocked, fused))
    assert np.linalg.norm(fused - blocked) / np.linalg.norm(blocked) < 0.01


# -- the expert layer ----------------------------------------------------------


def test_sigmoid_router_renormalised_and_scaled_matches_reference(key):
    s = sizes()
    layer = ref.init_layer(key, s, 2)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(9), (40, s.hidden))
    with jax.default_matmul_precision("highest"):
        chosen, weights = experts.route(layer, x, MODEL.moe)
        ref_chosen, ref_weights = ref.route(layer, x, s)
    assert np.array_equal(chosen, ref_chosen)
    np.testing.assert_allclose(weights, ref_weights, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-5)


def test_shares_and_the_shared_expert_add_up_to_the_uncut_layer(key):
    """16 experts as 0–7 and 8–15: the routed parts of the two shares, with
    the shared expert — which every chip computes alike — counted once, are
    the uncut reference's layer; the layer held whole gives it in one
    piece through the stack's block."""
    s = sizes()
    x = jax.random.normal(jax.random.PRNGKey(10), (40, s.hidden))
    with jax.default_matmul_precision("highest"):
        whole = ref.init_layer(key, s, 2)["moe"]
        want, _ = ref.expert_layer(whole, x, s, identity)
        total = sequence_ops.gated_mlp(whole["shared"], x)
        for share in (tuple(range(8)), tuple(range(8, 16))):
            layer = ref.init_layer(key, s, 2, experts_held=share)["moe"]
            np.testing.assert_array_equal(layer["shared"]["up"],
                                          whole["shared"]["up"])
            part, _ = experts.routed_experts(
                layer, x, dataclasses.replace(MODEL.moe, experts_held=share))
            total = total + part
        block = ref.init_layer(key, s, 2)
        h = x.reshape(1, 40, -1)
        got, _, _ = shortconv_moe.block(block, h, MODEL, "sliding_attention")
        mine, _ = ref.layer_forward(block, h, s, "sliding_attention")
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got, mine, rtol=1e-4, atol=1e-5)


# -- counts --------------------------------------------------------------------


def test_program_counts_reach_telemetry(key):
    s = sizes()
    model = registry.build_sequence_scorer(
        "TestLaguna", make_variables(key, s), WINDOW)
    from sparkdl_tpu.core import executor

    tokens = tokens_of(4, 3)
    with telemetry.Telemetry(name="t", out_dir="") as scope:
        out = executor.execute(model, tokens, batch_size=2)
        counters = scope.metrics.snapshot()["counters"]
    assert set(out) == {"pooled", "logprobs", "expert_counts"}
    assert counters[telemetry.M_SEQUENCE_GATED_ATTENTION_LAYERS] == 3 * 5
    assert counters[telemetry.M_MOE_SHARED_EXPERT_LAYERS] == 3 * 4
    assert counters[telemetry.M_SEQUENCE_WINDOW_ATTENTION_LAYERS] == 3 * 3
    assert counters[telemetry.M_MOE_ROUTED_TOKENS] == 3 * WINDOW * 4
    # two launches of 2 rows: a buffer of 2 × 40 × 4 = 320 pairs a layer is
    # one row tile of 512; a layer's visits are its tile once for each
    # expert that has a pair in it
    assert counters[telemetry.M_MOE_PRODUCT_ROW_TILES] == 2 * 4
    assert 2 * 4 < counters[telemetry.M_MOE_PRODUCT_VISITS] <= 2 * 4 * 16


def test_the_other_stacks_report_neither_gate_nor_shared_expert(key):
    """A stack whose layers hold neither reports neither count; every stack
    with expert layers reports the grouped kernel's visits."""
    mellum = _load("references/mellum2_moe.py")
    s = mellum.sizes(_config("tests", "rehearsal", "configs",
                             "testspan-windows.json"))
    variables = {**mellum.init_embed(key, s), **mellum.init_head(key, s),
                 "layers": [mellum.init_layer(key, s, i)
                            for i in range(s.layers)]}
    model = registry.build_sequence_scorer("TestSpanMoE", variables, WINDOW)
    counts = model.apply_batch(tokens_of(5, 2), batch_size=2)[
        telemetry.PROGRAM_COUNTS]
    assert telemetry.M_SEQUENCE_GATED_ATTENTION_LAYERS not in counts
    assert telemetry.M_MOE_SHARED_EXPERT_LAYERS not in counts
    assert counts[telemetry.M_MOE_PRODUCT_ROW_TILES].sum() == 5
    assert counts[telemetry.M_MOE_PRODUCT_VISITS].sum() > 5


# -- sizes and the FLOP count ---------------------------------------------------


def _parameters(s):
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(lambda: {
        **ref.init_embed(key, s), **ref.init_head(key, s),
        "layers": [ref.init_layer(key, s, i) for i in range(s.layers)]})
    return sum(a.size for a in jax.tree.leaves(shapes))


def test_the_parameter_counts_come_out_of_the_references_shapes():
    """The arithmetic of the cut: it holds 3,869,857,792 parameters (7.74
    GB in bfloat16), the published model 33,442,596,864 — the "33.4B" of its
    name, which a per-head gate gives and an element-wise one would not."""
    assert _parameters(ref.sizes(PUBLISHED)) == 3869857792
    whole = ref.sizes(dict(PUBLISHED, **PUBLISHED["published"]))
    assert whole.layers == 40 and whole.dense_layers == 1
    assert _parameters(whole) == 33442596864
    gates = 10 * 2048 * 48 + 30 * 2048 * 64
    assert gates == 4915200
    assert 33442596864 - gates + 40 * 2048 * 48 * 128 > 34e9 - 1e8


def test_the_registry_holds_the_published_widths():
    published = registry.SEQUENCE_MODELS["Laguna-XS.2"]
    assert list(published.layer_types) == PUBLISHED["published"][
        "layer_types"]
    assert list(published.layer_types[:5]) == PUBLISHED["layer_types"]
    assert (published.hidden, published.kv_heads, published.head_dim,
            published.dense_width, published.moe.expert_width) == (
                2048, 8, 128, 8192, 512)
    assert (published.moe.experts, published.moe.top_k,
            published.moe.scaling, published.moe.scoring, published.span,
            published.vocab) == (
                256, 8, 2.5, "sigmoid", 512, 100352)
    assert PUBLISHED["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer"]
    assert {"gating", "hidden_act", "router"} <= set(PUBLISHED["assumed"])


@pytest.fixture
def flops(monkeypatch):
    """benchmarks/flops_laguna.py, which takes its count of attended pairs
    from flops_window.py as the benchmark's own path finds it."""
    monkeypatch.setitem(sys.modules, "flops_window",
                        _load("flops_window.py"))
    return _load("flops_laguna.py")


@pytest.mark.parametrize("window", [16, 40])
def test_flops_laguna_against_a_hand_count(flops, window):
    full = window * (window + 1) // 2
    sliding = sum(min(t + 1, 8) for t in range(window))

    def attention(heads):
        return window * (2 * 64 * heads * 16 + 2 * 64 * 2 * 16 + 64 * heads)

    mlp = 3 * 64 * 32
    moe = window * (64 * 16 + 4 * mlp + mlp)
    assert flops.window_flops(CONFIG, window) == 2 * (
        2 * attention(6) + 3 * attention(8) + 2 * 6 * full * 2 * 16
        + 3 * 8 * sliding * 2 * 16 + window * 3 * 64 * 128 + 4 * moe
        + (window - 1) * 64 * 32)


def test_flops_laguna_at_the_published_widths(flops):
    """The arithmetic at the published widths: 5.060 TFLOP a window of 4,096 tokens, and
    its shares."""
    macs = flops.macs_per_window(PUBLISHED, 4096)
    total = 2 * sum(macs.values())
    assert abs(total / 1e12 - 5.060) < 0.005
    share = {k: 200 * v / total for k, v in macs.items()}
    for part, want in (("attention_projections_and_gate", 27.9),
                       ("routed_experts", 16.3), ("shared_expert", 2.0),
                       ("router", 0.3), ("dense_mlp", 8.2), ("head", 33.3)):
        assert abs(share[part] - want) < 0.1, part
    assert abs(share["full_attention_scores_values"]
               + share["sliding_attention_scores_values"] - 12.0) < 0.1
