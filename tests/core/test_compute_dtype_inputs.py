"""``ModelFunction.with_compute_dtype``: inputs whose spec is an integer
dtype pass uncast (a token id above 256 does not survive bfloat16), integer
outputs stay integers, weights that arrive in the compute dtype are not
copied — and a float model's path through it is bit-identical to the cast it
replaced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparkdl_tpu.core.model_function import ModelFunction, TensorSpec


def _old_with_compute_dtype(model, dtype):
    """The cast as it was before integer inputs passed uncast: every input to
    the compute dtype, every output to float32."""
    variables = jax.tree.map(
        lambda a: a.astype(dtype)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, model.variables)

    def fn(vs, x):
        x = jax.tree.map(lambda a: jnp.asarray(a).astype(dtype), x)
        return jax.tree.map(lambda o: o.astype(jnp.float32),
                            model.apply_fn(vs, x))

    return ModelFunction(fn, variables, model.input_spec, name=model.name)


def test_integer_inputs_pass_uncast():
    table = jnp.arange(2000, dtype=jnp.float32)[:, None] * jnp.ones((1, 4))

    def apply(vs, ids):
        assert ids.dtype == jnp.int32
        return {"rows": vs["table"][ids][..., 0], "ids": ids + 1}

    model = ModelFunction.fromFunction(apply, {"table": table},
                                       TensorSpec((None, 3), "int32"))
    ids = np.asarray([[257, 1023, 1999], [3, 258, 1025]], np.int32)
    out = model.with_compute_dtype(jnp.bfloat16).apply_batch(ids,
                                                             batch_size=2)
    assert out["ids"].dtype == np.int32
    np.testing.assert_array_equal(out["ids"], ids + 1)
    # the rows looked up are those of the ids given, in bfloat16's rounding
    want = np.asarray(table.astype(jnp.bfloat16).astype(jnp.float32))[ids]
    np.testing.assert_array_equal(out["rows"], want[..., 0])


def test_dict_specs_cast_floats_and_leave_integers():
    def apply(vs, x):
        assert x["ids"].dtype == jnp.int32
        assert x["scale"].dtype == jnp.bfloat16
        return x["scale"] * vs["w"][x["ids"]]

    model = ModelFunction.fromFunction(
        apply, {"w": jnp.arange(600, dtype=jnp.float32)},
        {"ids": TensorSpec((None,), "int32"),
         "scale": TensorSpec((None,), "float32")})
    out = model.with_compute_dtype(jnp.bfloat16).apply_batch(
        {"ids": np.asarray([300, 511], np.int32),
         "scale": np.asarray([1.0, 2.0], np.float32)}, batch_size=2)
    np.testing.assert_array_equal(out, [300.0, 1024.0])


def test_weights_in_the_compute_dtype_are_not_copied():
    w = {"a": jnp.ones((8, 8), jnp.bfloat16), "n": jnp.arange(3)}
    model = ModelFunction.fromFunction(lambda vs, x: x @ vs["a"], w,
                                       TensorSpec((None, 8), "float32"))
    cast = model.with_compute_dtype(jnp.bfloat16)
    assert cast.variables is w
    assert not hasattr(cast, "float_source")
    # float32 weights are cast once, and the source is kept for persistence
    w32 = {"a": jnp.ones((8, 8), jnp.float32)}
    model32 = ModelFunction.fromFunction(lambda vs, x: x @ vs["a"], w32,
                                         TensorSpec((None, 8), "float32"))
    cast32 = model32.with_compute_dtype(jnp.bfloat16)
    assert cast32.variables["a"].dtype == jnp.bfloat16
    assert cast32.float_source is model32
    assert cast32.with_compute_dtype(jnp.float32).float_source is model32


@pytest.mark.parametrize("name", ["TestNet", "InceptionV3"])
def test_image_rows_are_bit_identical_to_before(name):
    from sparkdl_tpu.models import registry

    model = registry.build_featurizer(name, weights="random", seed=3)
    h, w = registry.get_model_spec(name).input_size
    images = np.random.default_rng(5).integers(
        0, 256, size=(2, h, w, 3), dtype=np.uint8)
    new = model.with_compute_dtype(jnp.bfloat16).apply_batch(images,
                                                             batch_size=2)
    old = _old_with_compute_dtype(model, jnp.bfloat16).apply_batch(
        images, batch_size=2)
    assert new.dtype == np.float32
    np.testing.assert_array_equal(new, old)
    q_new = model.with_dtype("int8").apply_batch(images, batch_size=2)
    assert np.isfinite(q_new).all()
