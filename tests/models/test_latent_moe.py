"""The latent-attention sparse-expert scorer (models/latent_moe.py) against
the benchmark's plain reference (benchmarks/references/openpangu_moe.py, which
imports nothing of the program) at small sizes on the CPU: through the
transformer and collect(); attention alone; the share test; no pair dropped;
the program's counts in telemetry; the windows the stack's head fused; the
benchmark's FLOP count by hand. The mechanisms' kernels have test files of
their own (test_attention.py, test_experts.py, test_scoring_head.py)."""

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
from sparkdl_tpu.models import (attention, experts, latent_moe, registry,
                                scoring_head, sequence_ops)

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
    latent = functools.partial(
        attention.latent_attention, heads=config.heads, eps=config.eps,
        rotation=attention.Rope(config.theta), block=config.query_block)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(p, x, s, lambda a: a, block=5)
        got, fused = latent(p, x)
    assert fused == 0
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    # causal: a later token does not move an earlier one
    with jax.default_matmul_precision("highest"):
        moved, _ = latent(p, x.at[-1].add(1.0))
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
        total = sequence_ops.gated_mlp(whole["shared"], x)
        pairs = 0
        for share in (everything[i:i + 4] for i in range(0, 16, 4)):
            layer = ref.init_layer(key, s, 2, False, experts_held=share)
            # an expert's weights are its own, whichever share holds it
            np.testing.assert_array_equal(
                layer["moe"]["experts"]["up"],
                whole["experts"]["up"][share[0]:share[0] + 4])
            config = dataclasses.replace(MODEL.moe, experts_held=share)
            part, told = experts.routed_experts(layer["moe"], x, config)
            # ... and the reference given the same share gives the same part
            ref_part, _ = ref.routed_part(layer["moe"], x, s, lambda a: a,
                                          experts_held=share)
            np.testing.assert_allclose(part, ref_part, rtol=1e-4, atol=1e-5)
            total = total + part
            pairs += int(told["counts"].sum())
    assert pairs == 40 * 4          # every (token, expert) pair, exactly once
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_no_pair_is_dropped_under_a_skewed_router(key):
    """One held expert gets every token: the held experts' pairs pass the
    buffer, are computed in a further round all the same, and are counted."""
    s = sizes()
    layer = ref.init_layer(key, s, 2, False)["moe"]
    layer["router"] = layer["router"].at[:, 5].add(4.0 / s.hidden ** 0.5)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(7), (96, s.hidden)))
    config = dataclasses.replace(MODEL.moe, experts_held=(4, 5, 6, 7),
                                 capacity_factor=1.0)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.routed_part(layer, x, s, lambda a: a)
        got, told = jax.jit(
            lambda p, x: experts.routed_experts(p, x, config))(layer, x)
    chosen, counts, overflow = (told[name] for name in (
        "chosen", "counts", "overflow"))
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


def test_the_stack_counts_the_windows_its_head_fused(key, monkeypatch):
    """The latent-attention stack's forward with a head of 128 rows in
    bfloat16: the count reads 0 a window on the path that writes the logits
    and 1 through the kernel, and the log-probabilities agree."""
    s = sizes(vocab_size=128)
    variables = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                             make_variables(key, s))
    config = dataclasses.replace(MODEL, vocab=128, moe=dataclasses.replace(
        MODEL.moe, experts_held=tuple(CONFIG["experts_held"])))
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
