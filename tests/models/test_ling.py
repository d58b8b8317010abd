"""The pre-norm stack with what the ``bailing_hybrid`` family adds (models/
linear_attention.py: KDA, linear attention with the gated delta rule, its
chunked form and its kernel; attention.latent_attention without a query
latent, with a per-head gate and the interleaved rotary; experts.route
within groups; the SwiGLU clamps) against the benchmark's plain reference
(benchmarks/references/ling_hybrid.py, which imports nothing of the
program) at the ``TestLing`` sizes on the CPU: the whole stack through the
transformer and collect(); the chunked form against the reference's
recurrence a position at a time; the kernel, interpreted, from the raw
inputs against the chunked form at a window of no whole chunks, and the
terms it makes and its blocked inverse against the chunked form's and the
triangular solve; group-limited routing against
a choice made by hand; the interleaved rotary against the halves' on
permuted weights; the shares of the experts against the uncut layer; the
clamps; the counts in telemetry; the parameter and FLOP counts."""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from sparkdl_tpu.core import telemetry
from sparkdl_tpu.engine.dataframe import DataFrame
from sparkdl_tpu.ml import DeepSequenceScorer
from sparkdl_tpu.models import (attention, experts, linear_attention,
                                registry, sequence_ops, shortconv_moe)

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


ref = _load("references/ling_hybrid.py")


def _config(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = _config("tests", "rehearsal", "configs", "testling-windows.json")
PUBLISHED = _config("configs", "ling-3.0-flash-ep4.json")
MODEL = registry.SEQUENCE_MODELS["TestLing"]
WINDOW = 72                     # a chunk of 64 and 8 positions of the next


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
    return jax.random.PRNGKey(23)


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
        inputCol="tokens", modelName="TestLing",
        weights=make_variables(key, s), window=WINDOW, batchSize=2,
        expertCountsCol="experts")
    rows = sorted(scorer.transform(frame).collect(), key=lambda r: r["id"])
    with jax.default_matmul_precision("highest"):
        want = ref.forward(key, s, tokens)
    return tokens, rows, want


# -- the program against the reference ----------------------------------------

# float32 on both sides: the products' order, the chunked form against the
# recurrence a position at a time and the blocked soft-max against the
# masked one are all that differ (each layer alone agrees to 1e-6)
TOLERANCE = 2e-4


def test_scorer_matches_reference_through_transformer_and_collect(scored):
    tokens, rows, (pooled, logprobs, chosen) = scored
    assert [r["tokens"] for r in rows] == tokens.tolist()
    got = ([r["pooled"] for r in rows], [r["logprobs"] for r in rows])
    assert max(gaps(got, (pooled, logprobs))) < TOLERANCE
    assert all(r["logprobs"][-1] == 0.0 for r in rows)
    # layer 0 is dense; the six after it route
    assert len(chosen) == 6
    counts = np.stack([[np.bincount(row.ravel(), minlength=16)
                        for row in layer] for layer in chosen], 1)
    assert np.array_equal(
        np.asarray([r["experts"] for r in rows]).reshape(5, 6, 16), counts)


@pytest.mark.parametrize("fault", ["carry", "delta", "decay", "groups",
                                   "interleave"])
def test_each_fault_in_the_programs_place_fails_by_far(key, scored, fault):
    """The reference without one of the model's mechanisms moves the rows
    by a hundred times the tolerance or more: a program that lacked it
    would not pass."""
    tokens, rows, (pooled, logprobs, _) = scored
    with jax.default_matmul_precision("highest"):
        wrong = ref.forward(key, ref.without(sizes(), fault), tokens)
    assert max(gaps(wrong[:2], (pooled, logprobs))) > 100 * TOLERANCE


@pytest.mark.parametrize("layer", [0, 5])
def test_each_mixer_and_its_block_match_the_reference(key, layer):
    """A linear-attention block (dense) and the latent-attention one (with
    its expert layer) through ``shortconv_moe.block`` against the
    reference's, the clamps off; the mixer is read off the weights."""
    s = sizes()
    weights = ref.init_layer(key, s, layer)
    assert ("kda" in weights) == (layer == 0)
    h = jax.random.normal(jax.random.PRNGKey(layer), (2, WINDOW, s.hidden))
    with jax.default_matmul_precision("highest"):
        got, _, told = shortconv_moe.block(weights, h, MODEL)
        want, _ = ref.layer_forward(weights, h, s, s.layer_types[layer])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert list(told) == ["fused_linear" if layer == 0 else "fused"]
    assert np.asarray(told[list(told)[0]]).tolist() == [0, 0]


# -- linear attention: the chunked form and the kernel ------------------------


def _recurrence_inputs(seed, rows, window, heads=2, d=16):
    """q, k (normed, q scaled), v, g in (−5, 0) and β, float32."""
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    shape = (rows, window, heads, d)
    q = unit(jax.random.normal(k1, shape)) * d ** -0.5
    k = unit(jax.random.normal(k2, shape))
    v = jax.random.normal(k3, shape)
    g = -5.0 * jax.nn.sigmoid(jax.random.uniform(k4, shape, minval=-12,
                                                 maxval=2))
    beta = jax.nn.sigmoid(jax.random.normal(k5, (rows, window, heads)))
    return q, k, v, g, beta


@pytest.mark.parametrize("window", [40, 64, 200])
def test_the_chunked_form_matches_the_recurrence_a_position_at_a_time(
        window):
    """Chunks of 64 whose decays' ratios are taken in sub-chunks of 16, the
    last chunk padded, against the reference's ``lax.scan`` over positions:
    float32 at full precision on both sides, so 1e-5 (the rounding of a sum
    of 64 terms); decays as strong as −5 a position never overflow."""
    q, k, v, g, beta = _recurrence_inputs(3, 2, window)
    s = sizes()
    want = jnp.stack([ref.delta_rule(q[b], k[b], v[b], g[b], beta[b], s)
                      for b in range(2)])
    flat = [a.reshape(2, window, -1) for a in (q, k, v, g)]
    got, fused = linear_attention.delta_rule(*flat, beta, jnp.float32)
    assert int(fused) == 0
    np.testing.assert_allclose(got, want.reshape(2, window, -1), atol=1e-5)
    assert np.isfinite(np.asarray(got)).all()


def _chunked_form(q, k, v, g, beta):
    """``scan_chunks(chunk_terms(…))`` over (1, T, H · d) inputs, as (1, T,
    H · d), and the terms."""
    terms = linear_attention.chunk_terms(q, k, v, g, beta)
    o = jnp.transpose(linear_attention.scan_chunks(terms), (0, 2, 3, 1, 4))
    return o.reshape(1, -1, o.shape[3] * o.shape[4])[:, :q.shape[1]], terms


@pytest.mark.parametrize("window,heads,decay", [
    (200, 2, "drawn"),  # three whole chunks and a padded fourth, one step
    (200, 2, "floor"),  # every decay at the floor: the ratios at their most
    (384, 2, "drawn"),  # six whole chunks, three grid steps of two
    (384, 3, "drawn"),  # three heads: a head a step
])
def test_the_kernel_matches_the_chunked_form_at_no_whole_chunks(window,
                                                                heads,
                                                                decay):
    """``fused_delta_rule`` interpreted from the raw ``q``, ``k``, ``v``,
    ``g``, ``β`` (heads of 128) — its terms made a step's chunks at a time
    in the kernel, two heads a step where they divide H — against
    ``scan_chunks`` over ``chunk_terms``: bfloat16 operands into the four
    state products, float32 terms, accumulation and state — each output
    within 2 % of the outputs' largest."""
    q, k, v, g, beta = _recurrence_inputs(4, 1, window, heads=heads, d=128)
    if decay == "floor":
        g = jnp.full_like(g, -5.0)
    flat = [a.reshape(1, window, -1) for a in (q, k, v, g)]
    want, terms = _chunked_form(*flat, beta)
    got = linear_attention.fused_delta_rule(*flat, beta, interpret=True)
    assert got.dtype == jnp.bfloat16 and got.shape == want.shape
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=0.02 * scale)
    if decay == "drawn":        # at the floor e^{−5 · 64} crosses nothing
        # the state crosses every chunk: resetting it moves the last chunks
        last = terms["v"].shape[2] - 1
        assert float(jnp.abs(
            linear_attention.scan_chunks(terms)[:, :, last]
            - linear_attention.scan_chunks(
                {name: a[:, :, last:] for name, a in terms.items()})[
                :, :, 0]).max()) > 0.1 * scale


@pytest.mark.parametrize("scale", [0.1, 1.0])
def test_the_blocked_inverse_matches_the_triangular_solve(scale):
    """``(I + A)⁻¹`` by substitution in blocks — the diagonal blocks of 16
    doubled from 2, packed, then the block rows below them in order —, in
    float32 for three matrices at once against
    ``lax.linalg.triangular_solve``: within 1e-5 of the inverse's largest
    entry."""
    a = jnp.tril(jax.random.uniform(jax.random.PRNGKey(7), (3, 64, 64),
                                    minval=-scale, maxval=scale), -1)
    want = jax.lax.linalg.triangular_solve(
        a + jnp.eye(64), jnp.broadcast_to(jnp.eye(64), a.shape),
        left_side=True, lower=True, unit_diagonal=True)
    got = linear_attention._lower_inverse(a)
    np.testing.assert_allclose(got, want, atol=1e-5 * float(
        jnp.abs(want).max()), rtol=0)


def test_a_chunks_terms_in_the_kernel_match_the_chunked_forms():
    """What the kernel makes of a step's chunks from their own rows
    (``_terms_of_chunks``), float32 at full precision, against
    ``chunk_terms``' every term for every chunk of a window of three,
    decays at the floor in the last: 1e-5 of each term's largest."""
    q, k, v, g, beta = _recurrence_inputs(5, 1, 192, heads=1, d=128)
    g = g.at[:, 128:].set(-5.0)
    terms = linear_attention.chunk_terms(
        *[a.reshape(1, 192, -1) for a in (q, k, v, g)], beta)
    got = linear_attention._terms_of_chunks(
        *[a.reshape(3, 64, 128) for a in (q, k, g)], beta.reshape(3, 1, 64))
    for name, a in got.items():
        want = terms[name][0, 0]
        if name == "decay":
            a = a[..., 0]
        np.testing.assert_allclose(a, want, rtol=0, atol=1e-5 * float(
            jnp.abs(want).max()), err_msg=name)


def test_kda_reads_heads_and_taps_off_the_weights(key):
    s = sizes()
    p = ref.init_layer(key, s, 1)["kda"]
    x = jax.random.normal(jax.random.PRNGKey(2), (WINDOW, s.hidden))
    with jax.default_matmul_precision("highest"):
        got, fused = linear_attention.kda(p, x[None], s.eps, s.lower)
        want = ref.kda(p, x, s, identity)
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)
    assert int(fused) == 0


# -- routing within groups, the rotary, the clamps ----------------------------


def test_group_limited_routing_matches_a_choice_by_hand():
    """16 experts in 4 groups: a token keeps the 2 groups whose two best
    biased scores sum highest and takes its top 4 among their 8 experts —
    where the top 4 over all 16 would take another."""
    logits = np.full(16, -4.0, np.float32)
    # group 0 holds the single best expert, groups 2 and 3 the best pairs
    logits[[0, 1, 8, 9, 12, 13]] = [3.0, -3.0, 2.0, 1.9, 1.8, 1.7]
    x = np.eye(16, dtype=np.float32)[:1]          # the router's first row
    p = {"router": np.zeros((16, 16), np.float32), "expert_bias":
         np.zeros(16, np.float32)}
    p["router"][0] = logits
    chosen, weights = experts.route(p, x, MODEL.moe)
    assert sorted(np.asarray(chosen)[0].tolist()) == [8, 9, 12, 13]
    flat = dataclasses.replace(MODEL.moe, groups=1, topk_group=1)
    chosen, _ = experts.route(p, x, flat)
    assert sorted(np.asarray(chosen)[0].tolist()) == [0, 8, 9, 12]
    scores = 1 / (1 + np.exp(-logits[[8, 9, 12, 13]]))
    np.testing.assert_allclose(
        np.sort(np.asarray(weights)[0]),
        np.sort(scores / scores.sum() * 2.5), rtol=1e-6)


def test_group_limited_routing_matches_the_reference_with_its_bias(key):
    s = sizes()
    p = ref.init_layer(key, s, 3)["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (256, s.hidden))
    with jax.default_matmul_precision("highest"):
        chosen, weights = experts.route(p, x, MODEL.moe)
        want_chosen, want_weights = ref.route(p, x, s)
        flat, _ = ref.route(p, x, ref.without(s, "groups"))
    assert np.array_equal(np.sort(chosen, -1), np.sort(want_chosen, -1))
    np.testing.assert_allclose(np.sort(weights, -1),
                               np.sort(want_weights, -1), rtol=1e-6)
    # within groups the choice differs from the top 4 over all 16 often
    assert (np.sort(flat, -1) != np.sort(want_chosen, -1)).any(-1).mean() \
        > 0.2
    groups = np.asarray(chosen) // 4
    assert all(len(set(row)) <= 2 for row in groups.tolist())


def test_the_interleaved_rotary_is_the_halves_on_permuted_dimensions():
    """Pairing adjacent dimensions (2j, 2j + 1) is pairing a head's halves
    (j, j + r/2) once each head's rope columns are permuted so that 2j goes
    to j and 2j + 1 to j + r/2 — the same frequencies, the same scores."""
    T, heads, rope = 12, 3, 8
    x = jax.random.normal(jax.random.PRNGKey(6), (T, heads * rope))
    perm = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    columns = (np.arange(heads)[:, None] * rope + perm[None, :]).ravel()
    got = attention.rotary(x, 100.0, heads, interleave=True)
    want = attention.rotary(x[:, columns], 100.0, heads)
    np.testing.assert_allclose(got[:, columns], want, rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(got - attention.rotary(x, 100.0, heads)).max()) > 0.1


def test_the_latent_attention_matches_its_reference_and_turns_adjacent_pairs(
        key):
    s = sizes()
    p = ref.init_layer(key, s, 5)["mla"]
    x = jax.random.normal(jax.random.PRNGKey(7), (WINDOW, s.hidden))
    rotation = dict(MODEL.rope)["latent_attention"]
    assert rotation.interleave
    with jax.default_matmul_precision("highest"):
        got, fused = attention.latent_attention(p, x, s.heads, s.eps,
                                                rotation, 8)
        want = ref.mla(p, x, s, identity, block=8)
        halves = ref.mla(p, x, ref.without(s, "interleave"), identity,
                         block=8)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert int(fused) == 0
    assert float(jnp.abs(want - halves).max()) > 100 * 1e-5


@pytest.mark.parametrize("limit", [0.0, 0.5])
def test_the_clamps_in_the_gated_mlp_and_the_grouped_kernel(limit):
    """``silu(min(gate, L)) · clip(up, −L, L)``: the gated MLP against a hand
    count, and the grouped kernel's epilogue, interpreted, against the
    ragged products with the same limit (float32 operands, exact but for
    the order of a sum)."""
    rng = np.random.default_rng(8)
    x = rng.normal(size=(512, 128)).astype(np.float32)
    w = {name: rng.normal(size=shape).astype(np.float32) * 0.2
         for name, shape in (("gate", (128, 128)), ("up", (128, 128)),
                             ("down", (128, 128)))}
    gate, up = x @ w["gate"], x @ w["up"]
    if limit:
        gate, up = np.minimum(gate, limit), np.clip(up, -limit, limit)
    hand = (gate / (1 + np.exp(-gate)) * up) @ w["down"]
    with jax.default_matmul_precision("highest"):
        got = sequence_ops.gated_mlp(w, x, limit)
        sizes_ = jnp.asarray([200, 312], jnp.int32)
        stacked = {n: jnp.stack([a, a * 0.5]) for n, a in w.items()}
        kernel = experts.grouped_product(
            jnp.asarray(x), (stacked["gate"], stacked["up"]), sizes_,
            jnp.float32, limit=limit, interpret=True)
        ragged = jax.nn.silu(jnp.minimum(jax.lax.ragged_dot(
            x, stacked["gate"], sizes_), limit if limit else np.inf)) * (
            jnp.clip(jax.lax.ragged_dot(x, stacked["up"], sizes_),
                     -limit, limit) if limit else jax.lax.ragged_dot(
                x, stacked["up"], sizes_))
    np.testing.assert_allclose(got, hand, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(kernel, ragged, rtol=1e-4, atol=1e-4)
    if limit:
        assert float(jnp.abs(got - sequence_ops.gated_mlp(w, x)).max()) > 0.1


def test_the_clamped_layer_matches_the_reference(key):
    """TestLing's last layer holds limits (1.0 routed, 1.5 shared): the block
    with them against the reference's, and without them it differs."""
    s = sizes()
    assert s.limits[-1] == MODEL.swiglu_limits[-1] == (1.0, 1.5)
    weights = ref.init_layer(key, s, 6)
    h = 3 * jax.random.normal(jax.random.PRNGKey(9), (1, WINDOW, s.hidden))
    with jax.default_matmul_precision("highest"):
        got, _, _ = shortconv_moe.block(weights, h, MODEL, None, (1.0, 1.5))
        want, _ = ref.layer_forward(weights, h, s, "kda", limits=(1.0, 1.5))
        free, _, _ = shortconv_moe.block(weights, h, MODEL)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(free - got).max()) > 1e-3


# -- the experts' shares ------------------------------------------------------


def test_shares_and_the_shared_expert_add_up_to_the_uncut_layer(key):
    """16 experts in 4 groups held as 4 shares of a group each (as the
    deployment's 4 chips hold groups 0–1, 2–3, 4–5, 6–7 of 8): the routed
    parts of the four, with the shared expert — which every chip computes
    alike — counted once, are the uncut reference's layer."""
    s = sizes()
    x = jax.random.normal(jax.random.PRNGKey(10), (WINDOW, s.hidden))
    with jax.default_matmul_precision("highest"):
        whole = ref.init_layer(key, s, 2)["moe"]
        want, _ = ref.expert_layer(whole, x, s, identity)
        total = sequence_ops.gated_mlp(whole["shared"], x)
        for first in range(0, 16, 4):
            share = tuple(range(first, first + 4))
            layer = ref.init_layer(key, s, 2, experts_held=share)["moe"]
            np.testing.assert_array_equal(layer["shared"]["up"],
                                          whole["shared"]["up"])
            part, told = experts.routed_experts(
                layer, x, dataclasses.replace(MODEL.moe, experts_held=share))
            total = total + part
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


# -- counts -------------------------------------------------------------------


def test_program_counts_reach_telemetry(key):
    s = sizes()
    model = registry.build_sequence_scorer(
        "TestLing", make_variables(key, s), WINDOW)
    from sparkdl_tpu.core import executor

    tokens = tokens_of(4, 3)
    with telemetry.Telemetry(name="t", out_dir="") as scope:
        out = executor.execute(model, tokens, batch_size=2)
        counters = scope.metrics.snapshot()["counters"]
    assert set(out) == {"pooled", "logprobs", "expert_counts"}
    assert counters[telemetry.M_SEQUENCE_LINEAR_ATTENTION_LAYERS] == 3 * 6
    assert counters[telemetry.M_SEQUENCE_FUSED_LINEAR_ATTENTION_LAYERS] == 0
    assert counters[telemetry.M_MOE_GROUP_LIMITED_LAYERS] == 3 * 6
    assert counters[telemetry.M_MOE_SHARED_EXPERT_LAYERS] == 3 * 6
    assert counters[telemetry.M_SEQUENCE_FUSED_ATTENTION_LAYERS] == 0
    assert counters[telemetry.M_MOE_ROUTED_TOKENS] == 3 * WINDOW * 6
    assert telemetry.M_SEQUENCE_WINDOW_ATTENTION_LAYERS not in counters


def test_the_other_stacks_report_no_linear_attention_nor_groups(key):
    laguna = _load("references/laguna_moe.py")
    s = laguna.sizes(_config("tests", "rehearsal", "configs",
                             "testlaguna-windows.json"))
    variables = {**laguna.init_embed(key, s), **laguna.init_head(key, s),
                 "layers": [laguna.init_layer(key, s, i)
                            for i in range(s.layers)]}
    model = registry.build_sequence_scorer("TestLaguna", variables, 40)
    tokens = np.zeros((2, 40), np.int32)
    counts = model.apply_batch(tokens, batch_size=2)[telemetry.PROGRAM_COUNTS]
    assert telemetry.M_SEQUENCE_LINEAR_ATTENTION_LAYERS not in counts
    assert telemetry.M_MOE_GROUP_LIMITED_LAYERS not in counts


def test_the_builder_refuses_a_layer_with_two_mixers(key):
    s = sizes()
    variables = make_variables(key, s)
    variables["layers"][1]["mla"] = variables["layers"][5]["mla"]
    with pytest.raises(ValueError, match="its one mixer"):
        registry.build_sequence_scorer("TestLing", variables, WINDOW)


# -- sizes and the FLOP count -------------------------------------------------


def _parameters(s):
    key = jax.random.PRNGKey(0)
    shapes = jax.eval_shape(lambda: {
        **ref.init_embed(key, s), **ref.init_head(key, s),
        "layers": [ref.init_layer(key, s, i) for i in range(s.layers)]})
    return sum(a.size for a in jax.tree.leaves(shapes))


def test_the_parameter_counts_come_out_of_the_references_shapes():
    """The arithmetic of the cut: 5,169,366,976 parameters held, 10.34 GB
    in bfloat16 — layer 0 with its dense MLP, five KDA expert layers and the
    MLA one, 128 of 512 experts each, a quarter of the vocabulary twice."""
    s = ref.sizes(PUBLISHED)
    assert s.layer_types == ("kda",) * 4 + ("mla", "kda", "kda")
    assert s.dense_layers == 1 and len(s.experts_held) == 128
    assert s.experts == 512 and s.vocab == 39296
    assert _parameters(s) == 5169366976
    assert 2 * _parameters(s) / 1e9 > 0.25 * 16


def test_the_registry_holds_the_published_widths():
    published = registry.SEQUENCE_MODELS["Ling-3.0-flash"]
    assert (published.hidden, published.heads, published.head_dim,
            published.dense_width, published.moe.expert_width) == (
                2560, 32, 128, 6144, 768)
    assert (published.moe.experts, published.moe.top_k,
            published.moe.groups, published.moe.topk_group,
            published.moe.scaling, published.vocab) == (
                512, 8, 8, 4, 2.5, 157184)
    assert published.kda_lower_bound == PUBLISHED["kda_lower_bound"]
    assert [list(pair) for pair in zip(*published.swiglu_limits)] == [
        PUBLISHED["expert_swiglu_limit_list"],
        PUBLISHED["share_expert_swiglu_limit_list"]]
    assert dict(published.rope)["latent_attention"].theta == \
        PUBLISHED["rope_theta"]
    assert PUBLISHED["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size", "num_nextn_predict_layers"]
    assert {"kda_gate", "head_wise", "use_qk_norm"} <= set(
        PUBLISHED["assumed"])


@pytest.fixture
def flops():
    return _load("flops_ling.py")


@pytest.mark.parametrize("window", [16, 72])
def test_flops_ling_against_a_hand_count(flops, window):
    chunks = -(-window // 64)
    recurrence = 4 * chunks * (4 * 64 * 64 * 16 + 64 ** 3 // 6
                               + 3 * 64 * 16 * 16)
    kda = window * (5 * 64 * 64 + 2 * 64 * 4 + 3 * 64 * 4) + recurrence
    mla = window * (64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 64 * 4 + 64 * 64) \
        + 4 * window * (window + 1) // 2 * 40
    mlp = 3 * 64 * 32
    moe = window * (64 * 16 + 4 * mlp + mlp)
    assert flops.window_flops(CONFIG, window) == 2 * (
        6 * kda + mla + window * 3 * 64 * 128 + 6 * moe
        + (window - 1) * 64 * 32)
    assert flops.delta_kernel_ops(CONFIG, window) == 2 * recurrence


def test_flops_ling_at_the_published_widths(flops):
    """The arithmetic at the published widths: 23.24 TFLOP a window of
    16,384 tokens, and its shares; the recurrence's kernel 86.6 GFLOP and
    807 MB a call, bound by the bytes on a v5e."""
    macs = flops.macs_per_window(PUBLISHED, 16384)
    total = 2 * sum(macs.values())
    assert abs(total / 1e12 - 23.245) < 0.005
    share = {k: 200 * v / total for k, v in macs.items()}
    for part, want in (("kda_projections", 44.5), ("kda_recurrence", 2.2),
                       ("mla_scores_values", 11.8), ("routed_experts", 10.0),
                       ("shared_expert", 5.0), ("head", 14.2)):
        assert abs(share[part] - want) < 0.1, part
    assert abs(flops.delta_kernel_ops(PUBLISHED, 16384) / 1e9 - 86.6) < 0.1
    assert abs(flops.delta_kernel_bytes(PUBLISHED, 16384) / 1e6 - 807.4) < 0.1
