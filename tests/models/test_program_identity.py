"""Every sequence stack's whole program at its CPU test size lowers to the
text it lowered to before: the sha256 of the lowered text without debug
information, for the TPU and for the CPU, float32 weights drawn as the
benchmark draws the stack's (benchmarks/references/, shapes only).
A change that moves code between modules and means to leave every program
alone keeps these digests; one that means to alter a program computes them
anew."""

import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import pytest

from sparkdl_tpu.core import telemetry
from sparkdl_tpu.models import registry

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


def _config(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


# Each stack's reference module and rehearsal config. The digests of the four
# that came before the grouped kernel's visit counts are of the program as
# it lowered then, those two counts taken off its outputs (they are all it
# gained since); TestLaguna's are of the program as it lowered when the
# stacks' mechanisms moved to modules of their own, taken the same way;
# TestLing's of the program as it lowered when it came.
STACKS = {
    "TestShortConvMoE": ("lfm2_moe", "testshortconv-windows"),
    "TestSpanMoE": ("mellum2_moe", "testspan-windows"),
    "TestStateSpace": ("jamba_ssm", "teststatespace-windows"),
    "TestLatentMoE": ("openpangu_moe", "testmoe-windows"),
    "TestLaguna": ("laguna_moe", "testlaguna-windows"),
    "TestLing": ("ling_hybrid", "testling-windows"),
}
DIGESTS = {
    ("TestLatentMoE", "tpu"): "5a954e8a43953e3e",
    ("TestLatentMoE", "cpu"): "4f89cf6186888b19",
    ("TestShortConvMoE", "tpu"): "049ee87f0619f42e",
    ("TestShortConvMoE", "cpu"): "34ff37fcd8eeee45",
    ("TestSpanMoE", "tpu"): "40b8c5df13b2043b",
    ("TestSpanMoE", "cpu"): "4ccc815f9b929f94",
    ("TestStateSpace", "tpu"): "734e02ab47085d0c",
    ("TestStateSpace", "cpu"): "734e02ab47085d0c",
    ("TestLaguna", "tpu"): "4d48621aee1f6cb5",
    ("TestLaguna", "cpu"): "f4a34a768156b036",
    ("TestLing", "tpu"): "2fb828ab8a941917",
    ("TestLing", "cpu"): "768ea446b1d35e86",
}


def _stack_shapes(model):
    """The shapes of the model's test-size weights, drawn as the benchmark
    draws them (float32), and the experts they hold."""
    module, name = STACKS[model]
    other = _load(f"references/{module}.py")
    config = _config("tests", "rehearsal", "configs", f"{name}.json")
    s = other.sizes(config)
    key = jax.random.PRNGKey(0)

    def layer(i):
        if module == "jamba_ssm":
            return other.init_layer(key, s, i, other.is_attention(s, i))
        if module in ("lfm2_moe", "openpangu_moe"):
            return other.init_layer(key, s, i, i < s.dense_layers)
        return other.init_layer(key, s, i)

    variables = jax.eval_shape(lambda: {
        **other.init_embed(key, s), **other.init_head(key, s),
        "layers": [layer(i) for i in range(s.layers)]})
    if "head" not in variables:                 # a tied head
        variables["head"] = variables["embed"]
    return variables, config.get("experts_held")


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("model", sorted(STACKS))
def test_the_other_models_programs_are_theirs_but_for_the_visit_counts(
        model, platform):
    window = 32 if model in ("TestStateSpace", "TestLatentMoE") else 40
    variables, held = _stack_shapes(model)
    mf = registry.build_sequence_scorer(model, variables, window, held)
    visits = {telemetry.M_MOE_PRODUCT_VISITS,
              telemetry.M_MOE_PRODUCT_ROW_TILES}

    def program(vs, tokens):      # its name names the lowered module
        out = dict(mf.apply_fn(vs, tokens))
        out[telemetry.PROGRAM_COUNTS] = {
            k: v for k, v in out[telemetry.PROGRAM_COUNTS].items()
            if k not in visits}
        return out

    text = jax.jit(program).trace(
        variables, jax.ShapeDtypeStruct((2, window), jnp.int32)).lower(
            lowering_platforms=(platform,)).as_text(debug_info=False)
    digest = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert digest == DIGESTS[model, platform]
