#!/usr/bin/env python3
"""Ahead-of-time compiles, for a described ``v5e:2x2`` chip that is not
attached, of the programs the cells time and of the references that check
them; prints each one's ``memory_analysis()``. Run by hand, here, with
``JAX_PLATFORMS=cpu``: what the chip's compiler refuses costs no chip time.
A compile that passes is not a chip run; nothing here is a speed.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_compile.py [featurize] [fit] [references]
"""

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (HERE, os.path.dirname(HERE)):
    if path not in sys.path:
        sys.path.insert(0, path)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GIB = 2.0 ** 30


def one_chip():
    """The sharding of one described v5e chip (topology described here,
    inside a function, never at import)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def shapes_of(tree, sharding):
    import jax

    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                       sharding=sharding),
                        tree)


def report(name, lowered):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    m = compiled.memory_analysis()
    print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s; "
          f"temporaries {m.temp_size_in_bytes / GIB:.3f} GiB, arguments "
          f"{m.argument_size_in_bytes / GIB:.3f} GiB, outputs "
          f"{m.output_size_in_bytes / GIB:.3f} GiB, aliased "
          f"{m.alias_size_in_bytes / GIB:.3f} GiB", flush=True)


def featurize(chip, batch=1024):
    """The bf16 InceptionV3 featurize program at the cells' launch size, as
    the executor's choke point builds it (uint8 rows in)."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.engine.dataframe import EngineConfig
    from sparkdl_tpu.models import registry

    mf = registry.build_featurizer("InceptionV3", weights="random")
    variant = mf.flattened().with_dtype(EngineConfig.inference_precision)
    x = jax.ShapeDtypeStruct((batch, 299, 299, 3), jnp.uint8, sharding=chip)
    report(f"program: InceptionV3 featurize {EngineConfig.inference_precision}"
           f" b{batch}",
           jax.jit(variant.apply_fn).lower(shapes_of(variant.variables, chip),
                                           x))


def fit(chip, batch=128):
    """The bf16 ResNet50 train step at the cell's batch."""
    import jax
    import jax.numpy as jnp

    import harness
    from drivers.fit import Driver

    cell = harness.Cell("resnet50-train.fit")
    driver = Driver(cell, 0, {"root": harness.ROOT})
    driver.traffic = dict(cell.traffic, distinct=0, check_steps=0)
    # build the trainer as the driver does, without running a step
    from sparkdl_tpu.models import registry
    from sparkdl_tpu.train import Trainer
    import flops

    variables = jax.jit(lambda key: flops.init_variables(
        driver.reference, key, True))(jax.random.PRNGKey(0))
    spec = registry.get_model_spec(cell.config["model"])
    trainer, state = Trainer.from_flax(
        spec.builder(include_top=True, classes=spec.classes), variables,
        loss=cell.config["loss"], optimizer=cell.config["optimizer"],
        learning_rate=cell.config["learning_rate"],
        compute_dtype=cell.config["compute_dtype"])
    x = jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.float32, sharding=chip)
    y = jax.ShapeDtypeStruct((batch, 1000), jnp.float32, sharding=chip)
    report(f"program: ResNet50 train step bf16 b{batch}",
           trainer.make_train_step().lower(shapes_of(state, chip), x, y))


def references(chip):
    """The plain references at the sizes the checks run them."""
    import jax
    import jax.numpy as jnp

    import flops
    from references import inceptionv3, plain, resnet50

    with jax.default_matmul_precision("highest"):
        vs = jax.eval_shape(lambda: flops.init_variables(
            inceptionv3, jax.random.PRNGKey(0), False))

        def forward(vs, x):
            x = inceptionv3.preprocess(x.astype(jnp.float32))
            return inceptionv3.forward(plain.Scope.apply(vs), x)

        x = jax.ShapeDtypeStruct((64, 299, 299, 3), jnp.uint8, sharding=chip)
        report("reference: InceptionV3 float32 highest b64",
               jax.jit(forward).lower(shapes_of(vs, chip), x))

        vs = jax.eval_shape(lambda: flops.init_variables(
            resnet50, jax.random.PRNGKey(0), True))

        def step(params, stats, x, y):
            def loss_fn(params):
                scope = plain.Scope.apply({"params": params,
                                           "batch_stats": stats}, train=True)
                logits = resnet50.forward(scope, x, include_top=True)
                probs = jnp.clip(jax.nn.softmax(logits), 1e-7, 1 - 1e-7)
                return -jnp.mean(jnp.sum(y * jnp.log(probs), -1)), \
                    scope.new_stats
            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        x = jax.ShapeDtypeStruct((128, 224, 224, 3), jnp.float32,
                                 sharding=chip)
        y = jax.ShapeDtypeStruct((128, 1000), jnp.float32, sharding=chip)
        vs = shapes_of(vs, chip)
        report("reference: ResNet50 float32 highest loss+gradient b128",
               jax.jit(step).lower(vs["params"], vs["batch_stats"], x, y))


def main(argv):
    which = argv or ["featurize", "fit", "references"]
    chip = one_chip()
    for name in which:
        {"featurize": featurize, "fit": fit, "references": references}[name](
            chip)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
