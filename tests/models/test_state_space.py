"""The pre-norm stack with state-space mixers and no expert layer (models/
state_space.py and shortconv_moe.py; the ``jamba`` family) against the
benchmark's plain reference (benchmarks/references/jamba_ssm.py, which
imports nothing of the program) at the ``TestStateSpace`` sizes on the CPU:
through the transformer and collect() at float32 and at bfloat16, each
tolerance with its reason and tight enough that the faults a program of this
model can have — the state not handed over, the inner norms left out, float8
operands — fail it; the mixer alone; the selective-scan kernel, interpreted,
against the plain path; a window scanned in two halves; which path a lowering
takes; attention without a rotary at 20 query heads on one key head; the
counts of a stack without experts; the builder; the shared taps; the
parameter and FLOP counts by hand."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu.core import executor, telemetry
from sparkdl_tpu.engine.dataframe import DataFrame
from sparkdl_tpu.ml import DeepSequenceScorer
from sparkdl_tpu.models import latent_moe, registry, shortconv_moe, state_space

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


ref = _load("references/jamba_ssm.py")
check = _load("check.py")
CONFIG = json.load(open(os.path.join(
    BENCH, "tests", "rehearsal", "configs", "teststatespace-windows.json")))
PUBLISHED = json.load(open(os.path.join(BENCH, "configs", "jamba2-3b.json")))
MODEL = registry.SEQUENCE_MODELS["TestStateSpace"]
WINDOW = 64
SIZES = ref.sizes(CONFIG)


def make_variables(key, s=SIZES, dtype=jnp.float32):
    return jax.tree.map(lambda a: a.astype(dtype), {
        **ref.init_embed(key, s), **ref.init_head(key, s),
        "layers": [ref.init_layer(key, s, i) for i in range(s.layers)]})


def tokens_of(seed, rows):
    return np.random.default_rng(seed).integers(
        0, SIZES.vocab, size=(rows, WINDOW)).astype(np.int32)


def fp8(a):
    return a.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def reference_outputs(key, tokens, s=SIZES, quant=None):
    with jax.default_matmul_precision("highest"):
        return ref.forward(key, s, tokens, quant)


def gaps(got, want):
    """The benchmark's three numbers (``drivers/windows.py``): the worst row's
    angle between the pooled states, and of |Δ log p| over a row's positions
    the median and the 90th percentile, worst row."""
    gap = np.abs(np.asarray(got[1], np.float64) - want[1])[:, :-1]
    return (check.feature_angle_gap(got[0], want[0]),
            float(np.quantile(gap, 0.5, axis=1).max()),
            float(np.quantile(gap, 0.9, axis=1).max()))


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(23)


# What the program may differ from the reference by, as `gaps` reads it. At
# float32 only the order of sums differs (XLA's CPU dot, the blocked soft-max,
# the scan in blocks): the readings are 2e-7 / 2.4e-7 / 6e-7 over three sets
# of rows, the limit twenty times the largest. At bfloat16 every product's
# operands, x, z and the gated y are rounded to 8 bits of mantissa: the
# readings are 0.0070–0.0075 / 0.0049–0.0056 / 0.0145–0.0156, the limits
# three times that — and the faults read 0.21 / 0.10 / 0.52 (the state reset
# every 16 positions), 0.29 / 0.20 / 0.64 (no inner norms) and 0.14 / 0.10 /
# 0.25 (float8 operands): 5 to 14 times the limits (the next test).
LIMITS = {"float32": (1e-5, 1e-5, 1e-5), "bfloat16": (0.022, 0.017, 0.047)}


# -- the program against the reference ----------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_scorer_matches_reference_through_transformer_and_collect(key, dtype):
    tokens = tokens_of(1, 5)
    frame = DataFrame.fromArrow(pa.table({
        "id": pa.array(np.arange(5)),
        "tokens": pa.array(list(tokens), type=pa.list_(pa.int32()))}),
        numPartitions=2)
    scorer = DeepSequenceScorer(
        inputCol="tokens", modelName="TestStateSpace",
        weights=make_variables(key, dtype=jnp.dtype(dtype)), window=WINDOW,
        batchSize=2)
    rows = sorted(scorer.transform(frame).collect(), key=lambda r: r["id"])
    assert [r["tokens"] for r in rows] == tokens.tolist()
    assert all(r["logprobs"][-1] == 0.0 for r in rows)
    got = (np.asarray([r["pooled"] for r in rows], np.float32),
           np.asarray([r["logprobs"] for r in rows], np.float32))
    for value, limit in zip(gaps(got, reference_outputs(key, tokens)),
                            LIMITS[dtype]):
        assert value < limit


@pytest.mark.parametrize("fault", ["carry", "inner_norms", "float8"])
def test_a_fault_of_this_model_fails_the_tolerances(key, fault):
    """The reference with one thing left out, put in the program's place:
    the state reset every 16 positions (a blocked scan that drops the
    hand-over; the cell's control resets every 256 of 16,384), δ, B and C
    without their norms, and float8 operands in every product. Each fails
    every one of the bfloat16 limits four times over."""
    tokens = tokens_of(2, 2)
    want = reference_outputs(key, tokens)
    if fault == "float8":
        faulty = reference_outputs(key, tokens, quant=fp8)
    else:
        s = ref.without(SIZES, fault)
        if fault == "carry":
            assert s.carry_reset == ref.CARRY_RESET == 256
            s.carry_reset = 16
        faulty = reference_outputs(key, tokens, s)
    read = gaps(faulty, want)
    assert all(value > 4 * limit
               for value, limit in zip(read, LIMITS["bfloat16"])), read


def test_without_knows_its_two_faults():
    with pytest.raises(ValueError, match="nothing named"):
        ref.without(SIZES, "rotary")
    assert ref.without(SIZES, "inner_norms").inner_norms is False
    assert SIZES.inner_norms and SIZES.carry_reset == 0


def test_mixer_alone_matches_reference(key):
    s = SIZES
    p = ref.init_layer(key, s, 2)["ssm"]
    u = jax.random.normal(jax.random.fold_in(key, 1), (2, WINDOW, s.hidden))
    with jax.default_matmul_precision("highest"):
        want = ref.state_space(p, u, s, lambda a: a)
        got, last, fused = state_space.state_space(p, u, MODEL)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert last.shape == (2, s.d_state, s.d_inner)
    assert fused.tolist() == [0, 0]


def test_mixer_takes_a_state_and_returns_the_last(key):
    """The second half of a window from the state the first half left is the
    window's second half. (With taps that read the current position only:
    the convolution's three positions before a piece are the other thing a
    carried window needs, and the mixer starts them at zero.)"""
    s = SIZES
    p = ref.init_layer(key, s, 0)["ssm"]
    p["taps"] = p["taps"].at[:, :-1].set(0.0)
    u = jax.random.normal(jax.random.fold_in(key, 2), (1, WINDOW, s.hidden))
    half = WINDOW // 2
    whole, last, _ = state_space.state_space(p, u, MODEL)
    first, handed, _ = state_space.state_space(p, u[:, :half], MODEL)
    second, last_of_halves, _ = state_space.state_space(p, u[:, half:], MODEL,
                                                        handed)
    np.testing.assert_allclose(jnp.concatenate([first, second], 1), whole,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(last_of_halves, last, rtol=1e-5, atol=1e-6)
    dropped, _, _ = state_space.state_space(p, u[:, half:], MODEL)
    assert np.abs(np.asarray(dropped - second)).max() > 1e-3


# -- the selective scan: the kernel, interpreted, against the plain path -------


def scan_operands(window, channels, states=16, dtype=jnp.bfloat16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(k[0], (window, channels)).astype(dtype)
    z = jax.random.normal(k[1], (window, channels)).astype(dtype)
    delta = jax.nn.softplus(jax.random.normal(k[2], (window, channels)) - 3)
    a = -jnp.arange(1, states + 1, dtype=jnp.float32)[:, None] * jnp.ones(
        (states, channels))
    b = jax.random.normal(k[3], (window, states))
    c = jax.random.normal(k[4], (window, states))
    d = jnp.ones((channels,))
    return x, delta, a, b, c, d, z, jax.random.normal(k[5], (states, channels))


@pytest.mark.parametrize("window,channels,block,chunk,unroll", [
    (128, 512, 128, 512, 8), (256, 512, 128, 256, 4), (64, 1024, 32, 512, 1),
    (256, 256, 64, 256, 8)])
def test_kernel_interpreted_against_the_plain_path(window, channels, block,
                                                   chunk, unroll):
    """The last state bit for bit — both paths step through the positions in
    one order with one arithmetic — and ``y`` within one rounding to bfloat16
    of its value: the sum over a channel's 16 states runs down the sublanes
    in the kernel and in XLA's order on the plain path."""
    operands = scan_operands(window, channels)
    want, want_last = jax.jit(state_space.scan_blocks)(*operands)
    got, last = state_space.fused_selective_scan(
        *operands, block=block, chunk=chunk, unroll=unroll, interpret=True)
    assert got.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    assert np.array_equal(last, want_last)
    want, got = (np.asarray(v, np.float32) for v in (want, got))
    assert np.all(np.abs(got - want) <= 2.0 ** -7 * np.abs(want) + 1e-6)


def test_plain_path_against_a_loop_over_positions():
    """The contract itself, in numpy, a position at a time."""
    x, delta, a, b, c, d, z, s = (np.asarray(v, np.float64)
                                  for v in scan_operands(24, 8, 4, jnp.float32))
    want = []
    for t in range(24):
        s = np.exp(delta[t] * a) * s + (delta[t] * x[t]) * b[t][:, None]
        y = (s * c[t][:, None]).sum(0) + d * x[t]
        want.append(y * z[t] / (1 + np.exp(-z[t])))
    got, last = state_space.scan_blocks(*scan_operands(24, 8, 4, jnp.float32))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(last, s, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("path", ["plain", "kernel"])
def test_a_window_in_two_halves_equals_the_window_whole(path):
    operands = scan_operands(256, 512, seed=3)
    if path == "plain":
        scan = jax.jit(state_space.scan_blocks)
    else:
        def scan(*o):
            return state_space.fused_selective_scan(*o, interpret=True)

    def half(lo, hi, state):
        x, delta, a, b, c, d, z, _ = operands
        return scan(x[lo:hi], delta[lo:hi], a, b[lo:hi], c[lo:hi], d,
                    z[lo:hi], state)

    whole, last = scan(*operands)
    first, handed = half(0, 128, operands[-1])
    second, last_of_halves = half(128, 256, handed)
    assert np.array_equal(np.concatenate([first, second]), whole)
    assert np.array_equal(last_of_halves, last)
    # ... and not where the hand-over is dropped
    dropped, _ = half(128, 256, jnp.zeros_like(handed))
    assert not np.array_equal(dropped, second)


@pytest.mark.parametrize("case", [
    "lowered-for-tpu", "lowered-for-cpu", "float32", "window-100",
    "channels-384", "channels-256"])
def test_the_choice_follows_what_the_lowering_can_see(case):
    """The kernel is taken where the program is lowered for a TPU with
    bfloat16 ``x`` and ``z``, a window of whole time blocks and a ``d_inner``
    of whole channel chunks (512, or 256); the plain path everywhere else."""
    window = 100 if case == "window-100" else 2 * state_space.SCAN_TIME_BLOCK
    channels = int(case.split("-")[1]) if case.startswith("channels") else 512
    dtype = jnp.float32 if case == "float32" else jnp.bfloat16
    operands = jax.eval_shape(
        lambda: scan_operands(window, channels, dtype=dtype))
    platform = "cpu" if case == "lowered-for-cpu" else "tpu"
    text = jax.jit(state_space.selective_scan).trace(*operands).lower(
        lowering_platforms=(platform,)).as_text()
    assert text.count("tpu_custom_call") == (
        case in ("lowered-for-tpu", "channels-256"))
    if case == "lowered-for-tpu":
        assert "selective_scan" in text


@pytest.mark.parametrize("branch", ["tpu", "default"])
def test_either_branch_says_what_it_lowered(branch, monkeypatch):
    """``fused`` comes out of the branch that ran: 1 from the kernel's (run
    here interpreted, in the TPU branch's place), 0 from the plain one."""
    operands = scan_operands(128, 512, seed=5)
    if branch == "tpu":
        kernel = state_space.fused_selective_scan
        monkeypatch.setattr(
            state_space, "fused_selective_scan",
            lambda *o: kernel(*o, interpret=True))
        monkeypatch.setattr(
            state_space.lax, "platform_dependent",
            lambda *o, tpu, default: tpu(*o))
    y, last, fused = state_space.selective_scan(*operands)
    want, want_last = state_space.scan_blocks(*operands)
    assert int(fused) == (branch == "tpu")
    assert np.array_equal(last, want_last)
    assert np.allclose(np.asarray(y, np.float32),
                       np.asarray(want, np.float32), rtol=2.0 ** -7)


# -- attention without a rotary, 20 query heads on one key head ----------------


def dense_attention(q, k, v, heads):
    """A masked dense soft-max, every query head on the one key head."""
    T = q.shape[0]
    q = q.reshape(T, heads, -1).astype(np.float64)
    scores = np.einsum("qhd,kd->hqk", q, k.astype(np.float64))
    scores = np.where(np.tril(np.ones((T, T), bool)), scores, -np.inf)
    weights = np.exp(scores - scores.max(-1, keepdims=True))
    weights /= weights.sum(-1, keepdims=True)
    return np.einsum("hqk,kd->qhd", weights, v.astype(np.float64)).reshape(
        T, -1)


@pytest.mark.parametrize("path", ["blocked", "kernel"])
def test_twenty_query_heads_on_one_key_head_without_rotary(path):
    heads, width = 20, 128
    T = latent_moe.FUSED_QUERY_TILE if path == "kernel" else 48
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(k0, (T, heads * width)) * width ** -0.5
    k = jax.random.normal(k1, (T, width))
    v = jax.random.normal(k2, (T, width))
    if path == "blocked":
        out, fused, _ = latent_moe.grouped_causal_attention(q, k, v, heads, 16)
        assert int(fused) == 0
        tolerance = 1e-5
    else:
        q, k, v = (a.astype(jnp.bfloat16) for a in (q, k, v))
        out = latent_moe.fused_causal_attention(q, None, k, None, v,
                                                heads=heads, interpret=True)
        tolerance = 2e-2        # bfloat16 weights in the second product
    want = dense_attention(*(np.asarray(a, np.float32) for a in (q, k, v)),
                           heads)
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               atol=tolerance)


def test_attention_rotates_only_where_the_config_gives_a_theta(key):
    """Without a ``theta`` the layer is the reference's: no position enters
    but through the causal mask. With one, the same weights give another
    result."""
    s = SIZES
    p = ref.init_layer(key, s, 1)["attn"]
    x = jax.random.normal(jax.random.fold_in(key, 3), (WINDOW, s.hidden))
    with jax.default_matmul_precision("highest"):
        want = ref.attention(p, x, s, lambda a: a)
        got, fused, _ = shortconv_moe.grouped_attention(p, x, MODEL)
        turned, _, _ = shortconv_moe.grouped_attention(
            p, x, dataclasses.replace(MODEL, theta=10000.0))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert np.abs(np.asarray(turned) - want).max() > 0.01


# -- counts, the builder, the taps, the counts by hand --------------------------


def test_a_stack_without_experts_reports_its_counts_and_no_expert_counts(key):
    model = registry.build_sequence_scorer("TestStateSpace",
                                           make_variables(key), WINDOW)
    tokens = tokens_of(4, 3)
    with telemetry.Telemetry(name="t", out_dir="") as scope:
        out = executor.execute(model, tokens, batch_size=2)
        counters = scope.metrics.snapshot()["counters"]
    assert set(out) == {"pooled", "logprobs"}
    assert counters[telemetry.M_SEQUENCE_TOKENS] == 3 * WINDOW
    assert counters[telemetry.M_SEQUENCE_SSM_LAYERS] == 3 * 3
    # on a CPU no kernel: neither scan, attention nor head
    assert counters[telemetry.M_SEQUENCE_FUSED_SCAN_LAYERS] == 0
    assert counters[telemetry.M_SEQUENCE_FUSED_ATTENTION_LAYERS] == 0
    assert counters[telemetry.M_SEQUENCE_FUSED_HEAD_WINDOWS] == 0
    assert counters[telemetry.M_SEQUENCE_CONV_LAYERS] == 0
    assert not [name for name in counters if name.startswith("sparkdl.moe.")]
    assert telemetry.M_SEQUENCE_SCORED_KEYS not in counters


def test_the_other_stacks_report_no_state_space_counts(key):
    lfm2 = _load("references/lfm2_moe.py")
    config = json.load(open(os.path.join(
        BENCH, "tests", "rehearsal", "configs", "testshortconv-windows.json")))
    s = lfm2.sizes(config)
    embed = lfm2.init_embed(key, s)["embed"]
    variables = {"embed": embed, "head": embed,
                 "final_norm": lfm2.init_head(key, s)["final_norm"],
                 "layers": [lfm2.init_layer(key, s, i, i < s.dense_layers)
                            for i in range(s.layers)]}
    model = registry.build_sequence_scorer("TestShortConvMoE", variables, 40)
    out = model.apply_batch(tokens_of(5, 2)[:, :40] % s.vocab, batch_size=2)
    counts = out[telemetry.PROGRAM_COUNTS]
    assert telemetry.M_SEQUENCE_SSM_LAYERS not in counts
    assert telemetry.M_SEQUENCE_FUSED_SCAN_LAYERS not in counts
    assert telemetry.M_MOE_LOCAL_PAIRS in counts and "expert_counts" in out


def test_expert_counts_of_a_stack_without_experts_are_refused(key):
    frame = DataFrame.fromArrow(pa.table({
        "tokens": pa.array(list(tokens_of(6, 2)),
                           type=pa.list_(pa.int32()))}))
    scorer = DeepSequenceScorer(
        inputCol="tokens", modelName="TestStateSpace",
        weights=make_variables(key), window=WINDOW, batchSize=2,
        expertCountsCol="experts")
    with pytest.raises(ValueError, match="hold no expert layer"):
        scorer.transform(frame).collect()


@pytest.mark.parametrize("mixers", [("ssm", "attn"), (), ("conv", "ssm")])
def test_builder_wants_exactly_one_mixer_a_layer(key, mixers):
    variables = make_variables(key)
    donor = {"ssm": variables["layers"][0]["ssm"],
             "attn": variables["layers"][1]["attn"], "conv": {}}
    layer = {k: v for k, v in variables["layers"][0].items() if k != "ssm"}
    variables["layers"][0] = {**layer, **{m: donor[m] for m in mixers}}
    with pytest.raises(ValueError, match="its one mixer"):
        registry.build_sequence_scorer("TestStateSpace", variables, WINDOW)


def test_builder_wants_the_state_space_sizes_of_a_state_space_layer(key):
    with pytest.raises(ValueError, match="no state-space sizes"):
        registry.build_sequence_scorer(
            dataclasses.replace(MODEL, d_inner=0), make_variables(key),
            WINDOW)


def test_builder_reads_an_all_dense_stack_off_the_weights(key):
    """Any leading layers of the period, the vocabulary by the embedding's
    rows, no experts to hold: the config's expert fields stay at none."""
    variables = make_variables(key)
    variables["layers"] = variables["layers"][:2]
    model = registry.build_sequence_scorer("TestStateSpace", variables, WINDOW)
    out = model.apply_batch(tokens_of(7, 2), batch_size=2)
    assert out[telemetry.PROGRAM_COUNTS][
        telemetry.M_SEQUENCE_SSM_LAYERS].tolist() == [1, 1]
    assert MODEL.experts == 0 and MODEL.experts_held == () and MODEL.top_k == 0
    assert MODEL.theta is None


@pytest.mark.parametrize("bias", [False, True])
def test_the_shared_taps_against_the_references(key, bias):
    """One loop for two families: LFM2's gated convolution has no bias, this
    mixer's has one."""
    z = jax.random.normal(key, (2, 20, 8))
    taps = jax.random.normal(jax.random.fold_in(key, 1), (8, 4))
    b = jax.random.normal(jax.random.fold_in(key, 2), (8,)) if bias else None
    got = latent_moe.causal_taps(z, taps, b)
    if bias:
        want = ref.conv_taps(z, taps, b)
    else:
        lfm2 = _load("references/lfm2_moe.py")
        want = jnp.stack([lfm2.conv_taps(row, taps) for row in z])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # causal: a later position moves no earlier output
    moved = latent_moe.causal_taps(z.at[:, 10].add(1.0), taps, b)
    assert np.array_equal(moved[:, :10], got[:, :10])


def test_published_sizes_and_the_parameter_count():
    """ISSUE 44's count, from the reference's own arrays at the published
    configuration (shapes only): 3,029,337,472."""
    s = ref.sizes(PUBLISHED)
    key = jax.random.PRNGKey(0)

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))

    ssm = count(jax.eval_shape(lambda: ref.init_layer(key, s, 0)))
    attention = count(jax.eval_shape(lambda: ref.init_layer(key, s, 7)))
    embed = count(jax.eval_shape(lambda: ref.init_embed(key, s)))
    assert (ssm, attention, embed) == (104161472, 76682240, 167772160)
    kinds = [ref.is_attention(s, i) for i in range(s.layers)]
    assert [i for i, kind in enumerate(kinds) if kind] == [7, 21]
    assert 26 * ssm + 2 * attention + embed + s.hidden == 3029337472 \
        == PUBLISHED["published"]["parameters_counted_from_the_config"]
    published = registry.SEQUENCE_MODELS["AI21-Jamba2-3B"]
    assert (published.hidden, published.heads, published.kv_heads,
            published.head_dim, published.dense_width, published.vocab,
            published.d_inner, published.d_state, published.dt_rank,
            published.d_conv, published.eps, published.theta) == (
        s.hidden, s.heads, s.kv_heads, s.head_dim, s.dense_width, s.vocab,
        s.d_inner, s.d_state, s.dt_rank, s.taps, s.eps, None)
    assert PUBLISHED["reduced"] == [] and PUBLISHED["num_hidden_layers"] == 28


@pytest.mark.parametrize("window", [16, 40])
def test_flops_ssm_against_a_hand_count(window):
    flops = _load("flops_ssm.py")
    mixer = 64 * 256 + 128 * (8 + 32) + 8 * 128 + 128 * 64 + 128 * 4
    attention = 2 * 64 * 64 + 2 * 64 * 16
    mlp = 3 * 64 * 128
    pairs = window * (window + 1) // 2
    scan = 7 * window * 128 * 16
    assert flops.layer_kinds(CONFIG) == (3, 1)
    assert flops.scan_kernel_ops(CONFIG, window) == scan
    assert flops.scan_kernel_bytes(CONFIG, window) == window * (
        128 * 10 + 2 * 16 * 4)
    assert flops.window_flops(CONFIG, window) == 2 * (
        window * (3 * mixer + attention + 4 * mlp) + 4 * pairs * 2 * 16
        + (window - 1) * 64 * 64) + 3 * scan


def test_flops_ssm_at_the_published_widths():
    """ISSUE 44's own arithmetic: 6,052.7 MFLOP of products a token, 2.75
    TFLOP of scores and values, 101.9 TFLOP a window of 16,384 before the
    recurrence's 0.24; one layer's scan 9.4 GFLOP and 841 MB."""
    flops = _load("flops_ssm.py")
    macs = flops.macs_per_window(PUBLISHED, 16384)
    tera = {k: 2 * v / 1e12 for k, v in macs.items()}
    assert abs(tera["attention_scores_values"] - 2.749) < 0.001
    products = sum(v for k, v in tera.items() if k != "ssm_taps")
    assert abs(products - 101.9) < 0.05
    assert flops.scan_kernel_ops(PUBLISHED, 16384) == 9395240960
    assert flops.scan_kernel_bytes(PUBLISHED, 16384) == 16384 * 51328
    assert abs(flops.window_flops(PUBLISHED, 16384) / 1e12 - 102.18) < 0.05
    with pytest.raises(ValueError, match="without expert layers"):
        flops.layer_kinds(dict(PUBLISHED, num_experts=16))
