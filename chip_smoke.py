#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main paths once, on ONE TPU chip, in ONE process, through the
entry points a user calls, at the full width of a model the repo supports
(weights random from a seed), and checks every answer against a plain
reference:

  featurize  256 seeded JPEGs -> sparkdl_tpu.readImages ->
             DeepImageFeaturizer(InceptionV3, batchSize=128) -> collect()
  serving    ResidencyManager + ModelRegistry + ModelServer: single-row and
             batch predicts, evict, cold reload
  train      Trainer.from_flax(ResNet50) b64 224x224 bf16, three fit() steps

``--chips 4`` runs ONLY the single-process mesh path and what it is compared
with (4-device featurize and one data-parallel train step against their
one-device twins).

Output: one JSON object per phase on its own line, then — last line, only on
success — ``{"ok": true, "device": {"platform", "kind", "count"}}`` as JAX
reports the device. It refuses to run without a TPU (exit code != 0, no
result line), sets no JAX_PLATFORMS, starts no process that needs the chip,
and lets a failing phase's exception end the run.

The phases are functions of their model name and sizes so that a CPU
rehearsal (tests/test_chip_smoke.py) can call them with TestNet and a handful
of rows; the script itself has no such option.
"""

import argparse
import contextlib
import json
import os
import sys
import tempfile
import time

import numpy as np

# before JAX: the package init places the persistent compilation cache
# ($JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache) in the environment
import sparkdl_tpu

# docs/PERF.md "Inference precision": bf16 within 0.05 of fp32 on bounded
# heads. Random-weight features are far smaller than 1, where that bound
# alone would pass an all-zero answer — so the delta is also held to the
# same fraction of the reference's own largest value.
BF16_CONTRACT = 0.05
# Data parallelism. tests/train/test_trainer.py holds a mesh step to the
# one-device step within rtol 2e-4 / atol 2e-5 — on a small MLP. A ResNet50
# step from random weights is far worse conditioned (gradient norms in the
# hundreds: rounding differences of 1e-6 in the forward pass come back as
# percents of the update, in float32 as much as in bfloat16), so that
# tolerance is reported, not asserted. What is asserted is measured against
# the computation's own noise floor: the SAME one-device step on the SAME
# batch with its rows permuted is mathematically identical (batch statistics,
# the loss mean and the gradient sums are all permutation-invariant) and
# differs only by summation order — exactly what partitioning changes. The
# mesh step may be this many times that far from the one-device step (or
# DP_EXACT of the update, where the floor itself is at rounding level); a
# mesh step that dropped the all-reduce or trained on one shard is off by
# the whole update. In bfloat16 the floor itself is of the order of the
# update (the step is noise from random weights), so only the float32 step
# can tell a broken mesh from a sound one — it is also held to the loss of
# the one-device step, which every row of the global batch enters.
DP_RTOL, DP_ATOL = 2e-4, 2e-5
DP_NOISE_FACTOR = 4.0
DP_EXACT = 1e-4
DP_FLOAT32_LOSS_RTOL = 1e-4

# ---------------------------------------------------------------------------
# device + compile accounting
# ---------------------------------------------------------------------------


def require_tpu(count):
    """The device line of the result; raises SystemExit BEFORE anything is
    built when JAX's first device is not a TPU or the chip count is not the
    one this run is for."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {first.platform!r} "
            f"({first.device_kind}); run it on the chip machine with "
            "JAX_PLATFORMS unset")
    if len(devices) != count:
        raise SystemExit(
            f"chip_smoke: this run is for {count} chip(s), JAX found "
            f"{len(devices)}")
    return {"platform": first.platform, "kind": first.device_kind,
            "count": len(devices)}


class CompileMeter:
    """Seconds JAX spent in backend compiles (or loading them from the
    persistent cache) and how many requests the cache answered, from JAX's
    own monitoring events — the one clock that covers the engine's jits
    and the Trainer's step alike."""

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return self.seconds, self.cache_hits, self.cache_misses


def run_phase(meter, name, fn, *args, **kwargs):
    """Run one phase and print its JSON line. A failure prints ``ok:
    false`` and re-raises — nothing is passed over."""
    s0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    try:
        facts = fn(*args, **kwargs)
    except BaseException as e:
        print(json.dumps({"phase": name, "ok": False,
                          "seconds": round(time.perf_counter() - t0, 3),
                          "error": f"{type(e).__name__}: {e}"}), flush=True)
        raise
    s1, h1, m1 = meter.snapshot()
    print(json.dumps({"phase": name, "ok": True,
                      "seconds": round(time.perf_counter() - t0, 3),
                      "compile_seconds": round(s1 - s0, 3),
                      "compile_cache_hits": h1 - h0,
                      "compile_cache_misses": m1 - m0, **facts},
                     default=str), flush=True)
    return facts


def check(condition, message):
    """``assert`` that survives ``python -O``."""
    if not condition:
        raise AssertionError(message)


def on_platform(tree, platform):
    """Every array leaf of ``tree`` lives on ``platform`` devices — asserted
    on results BEFORE they are fetched to the host."""
    import jax

    leaves = [leaf for leaf in jax.tree_util.tree_leaves(tree)
              if isinstance(leaf, jax.Array)]
    check(leaves, "no device array to check")
    for leaf in leaves:
        check({d.platform for d in leaf.devices()} == {platform},
              f"result on {leaf.devices()}, expected {platform}")


def check_features(got, want, what):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    check(got.shape == want.shape,
          f"{what}: shape {got.shape}, expected {want.shape}")
    check(np.isfinite(got).all(), f"{what}: non-finite values")
    delta = float(np.max(np.abs(got - want)))
    bound = BF16_CONTRACT * min(1.0, float(np.max(np.abs(want))))
    check(delta <= bound,
          f"{what}: max |delta| {delta:.4g} vs the plain reference exceeds "
          f"the bf16 contract ({bound:.4g} at this output scale)")
    return delta


# ---------------------------------------------------------------------------
# data (made from the seed)
# ---------------------------------------------------------------------------


def write_jpegs(directory, n, seed, around=(375, 500)):
    """``n`` seeded JPEGs of mixed sizes around ``around`` (portrait and
    landscape, +-12%): smooth random fields, so files are photo-sized
    rather than noise-sized."""
    from PIL import Image

    rng = np.random.default_rng(seed)
    for i in range(n):
        h, w = (int(d * rng.uniform(0.88, 1.12)) for d in around)
        if rng.random() < 0.3:
            h, w = w, h
        coarse = rng.integers(0, 256, size=(12, 16, 3), dtype=np.uint8)
        img = Image.fromarray(coarse).resize((w, h), Image.BICUBIC)
        img.save(os.path.join(directory, f"img_{i:04d}.jpg"), quality=90)


def staged_batch(structs, input_size):
    """The batch the featurize path stages for ragged sources: decoded
    structs host-resized to the model input, uint8 (the transformer's own
    staging function, so the reference sees the same pixels)."""
    from sparkdl_tpu.image import imageIO

    batch, kept, dropped = imageIO.imageStructsToBatchArrayTolerant(
        structs, target_size=input_size, dtype=None)
    check(dropped == 0 and len(kept) == len(structs),
          f"{dropped} of {len(structs)} decoded images could not be staged")
    return batch


def reference_features(model_name, batch, chunk):
    """The plain reference: the same model's float32 ``apply_fn`` (random
    weights from the registry's seed, normalisation included) under one
    ``jax.jit`` at full matmul precision, on the same staged batch."""
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models import registry

    mf = registry.build_featurizer(model_name, weights="random")
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda vs, x: mf.apply_fn(vs, x.astype(jnp.float32)))
        outs = []
        for lo in range(0, len(batch), chunk):
            out = fn(mf.variables, batch[lo:lo + chunk])
            outs.append(np.asarray(out, np.float32).reshape(len(out), -1))
    return np.concatenate(outs)


def featurize_files(featurizer, directory):
    """The user path: files -> readImages -> featurizer -> collect(), as
    ``(rows sorted by file, (n, dim) float32 features)``."""
    rows = featurizer.transform(sparkdl_tpu.readImages(directory)).collect()
    rows.sort(key=lambda r: r["image"]["origin"])
    return rows, np.stack([np.asarray(r["features"], np.float32)
                           for r in rows])


def compiled_program(model, mesh=None):
    """The compiled ``batch -> output`` the engine's choke point launches
    for ``model`` (same precision variant, same jit cache entry — calling
    it compiles nothing new), so a result can be looked at ON the device
    before it is fetched."""
    from sparkdl_tpu.engine.dataframe import EngineConfig

    variant = model.with_dtype(EngineConfig.inference_precision)
    return variant, variant.jitted(
        mesh=mesh, donate_batch=EngineConfig.inference_donate_buffers)


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_featurize(model_name, n_images, batch_size, seed, platform,
                    around=(375, 500)):
    """files -> readImages -> DeepImageFeaturizer -> collect(), checked
    against the plain reference. Returns ``(facts, batch, features)``: the
    facts for the log, and the staged batch and features the serving phase
    answers against."""
    from sparkdl_tpu.core import telemetry
    from sparkdl_tpu.ml import DeepImageFeaturizer
    from sparkdl_tpu.models import registry
    from sparkdl_tpu.native import loader as native_loader

    spec = registry.get_model_spec(model_name)
    featurizer = DeepImageFeaturizer(inputCol="image", outputCol="features",
                                     modelName=model_name,
                                     batchSize=batch_size)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as directory:
        write_jpegs(directory, n_images, seed, around)
        with telemetry.Telemetry(name="chip_smoke.featurize",
                                 out_dir="") as tel:
            rows, features = featurize_files(featurizer, directory)
            compile_spans = len(tel.tracer.spans(telemetry.SPAN_COMPILE))
    check(features.shape == (n_images, spec.feature_dim),
          f"features of shape {features.shape}")

    batch = staged_batch([r["image"] for r in rows], spec.input_size)
    reference = reference_features(model_name, batch, batch_size)
    delta = check_features(features, reference, "featurize")

    # the path's own compiled program, called once more by hand: the result
    # must be ON the chip before it is fetched, and be what collect() gave
    _, program = compiled_program(
        featurizer._model_function("featurize").flattened())
    out = program(batch[:batch_size])
    on_platform(out, platform)
    check_features(out, features[:batch_size], "compiled fn vs collect()")

    return {
        "model": model_name, "rows": n_images,
        "feature_shape": list(features.shape),
        "max_abs_delta_vs_reference": delta,
        "reference_max_abs": float(np.max(np.abs(reference))),
        "decode_path": ("native library" if native_loader.available()
                        else "PIL"),
        "compile_spans": compile_spans,
    }, batch, features


def phase_serving(model_name, batch, features, batch_size, platform,
                  n_single=3):
    """Deploy v1 behind the residency manager; a few single-row predicts,
    one batch predict, evict, one more predict (cold reload) — every
    answer must match the featurize phase's row for the same image."""
    from sparkdl_tpu.core import health, telemetry
    from sparkdl_tpu.core.health import HealthMonitor
    from sparkdl_tpu.models import registry as model_registry
    from sparkdl_tpu.serving import (ModelRegistry, ModelServer,
                                     ResidencyManager)

    res = ResidencyManager(budget_bytes=2 << 30)
    reg = ModelRegistry(residency=res)
    srv = ModelServer(reg)
    reg.deploy("featurizer", "v1", batch_size=batch_size,
               loader=lambda: model_registry.build_featurizer(
                   model_name, weights="random"))
    rows = batch.astype(np.float32)  # the serving contract: float32 rows
    deltas = []
    with HealthMonitor("chip_smoke.serving") as mon, \
            telemetry.Telemetry(name="chip_smoke.serving",
                                out_dir="") as tel:
        for i in range(n_single):
            got = srv.predict("featurizer", rows[i])
            check(got.version == "v1" and got.output.ndim == 1,
                  f"single-row predict answered {got!r}, output shape "
                  f"{got.output.shape}")
            deltas.append(check_features(got.output, features[i],
                                         f"predict row {i}"))
        n_batch = min(8, len(rows))
        got = srv.predict("featurizer", rows[:n_batch])
        deltas.append(check_features(got.output, features[:n_batch],
                                     "batch predict"))

        res.pin("featurizer", "v1", False)  # the registry pins the active
        check(res.evict("featurizer", "v1")
              and not res.is_resident("featurizer", "v1"),
              "the deployed version could not be evicted")
        res.pin("featurizer", "v1", True)
        t0 = time.perf_counter()
        got = srv.predict("featurizer", rows[-1])
        cold_s = time.perf_counter() - t0
        deltas.append(check_features(got.output, features[-1],
                                     "predict after evict"))
        check(mon.count(health.SERVING_COLD_START) >= 1,
              "the predict after evict recorded no cold start")
        loads = len(tel.tracer.spans(telemetry.SPAN_MODEL_LOAD))

        variant, program = compiled_program(reg.model("featurizer"))
        on_platform(program(rows[:1]), platform)
        on_platform(variant.variables, platform)
    return {"model": model_name, "predicts": n_single + 2,
            "max_abs_delta_vs_featurize": max(deltas),
            "cold_reload_seconds": round(cold_s, 3),
            "model_load_spans": loads,
            "evictions": res.status().get("evictions")}


def make_trainer(model_name, seed, mesh=None, compute_dtype="bfloat16"):
    import jax
    import jax.numpy as jnp

    from sparkdl_tpu.models import registry
    from sparkdl_tpu.train import Trainer

    spec = registry.get_model_spec(model_name)
    module = spec.builder(include_top=True, classes=spec.classes)
    h, w = spec.input_size
    variables = jax.jit(module.init)(jax.random.PRNGKey(seed),
                                     jnp.zeros((1, h, w, 3), jnp.float32))
    return spec, Trainer.from_flax(
        module, variables, loss="categorical_crossentropy", optimizer="sgd",
        learning_rate=0.01, mesh=mesh, compute_dtype=compute_dtype)


def train_batches(spec, batch_size, steps, seed):
    rng = np.random.default_rng(seed)
    h, w = spec.input_size
    out = []
    for _ in range(steps):
        x = rng.uniform(0, 1, size=(batch_size, h, w, 3)).astype(np.float32)
        y = np.eye(spec.classes, dtype=np.float32)[
            rng.integers(0, spec.classes, size=batch_size)]
        out.append((x, y))
    return out


def phase_train(model_name, batch_size, steps, seed, platform):
    """``steps`` steps through Trainer.fit: the step counter advanced, the
    loss is finite, parameters moved."""
    import jax

    from sparkdl_tpu.train.metrics import MetricsLogger

    spec, (trainer, state) = make_trainer(model_name, seed)
    before = jax.device_get(state.params)
    log = MetricsLogger(sinks=[lambda record: None])  # history only
    state = trainer.fit(state, train_batches(spec, batch_size, steps, seed),
                        epochs=1, metrics_logger=log)
    on_platform(state.params, platform)
    check(int(state.step) == steps, f"state.step is {int(state.step)}")
    losses = [record["loss"] for record in log.history]
    check(len(losses) == steps and np.isfinite(losses).all(),
          f"losses {losses}")
    after = jax.device_get(state.params)
    moved = sum(
        not np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(before),
            jax.tree_util.tree_leaves(after)))
    check(moved >= 1, "no parameter leaf changed")
    return {"model": model_name, "batch": batch_size, "steps": steps,
            "losses": losses, "param_leaves_changed": moved}


# ---------------------------------------------------------------------------
# the mesh path (--chips 4)
# ---------------------------------------------------------------------------


def on_all_devices(tree, devices, what):
    """Every array leaf of ``tree`` is fully replicated across ALL of
    ``devices`` — code that has only ever seen one chip may have put
    everything on the first."""
    import jax

    want = set(devices)
    for leaf in jax.tree_util.tree_leaves(tree):
        check(leaf.sharding.is_fully_replicated
              and set(leaf.devices()) == want
              and {s.device for s in leaf.addressable_shards} == want,
              f"{what}: a leaf is {leaf.sharding} on {leaf.devices()}, "
              f"not replicated on all of {sorted(map(str, want))}")


def phase_mesh_featurize(model_name, n_images, batch_size, seed, n_devices,
                         around=(375, 500)):
    """DeepImageFeaturizer over a ``data=n`` mesh against the same rows
    with ``mesh=None``; the path's own compiled program must shard its
    batch over n distinct devices with its variables on all of them."""
    import jax

    from sparkdl_tpu.core.mesh import MeshConfig, make_mesh
    from sparkdl_tpu.ml import DeepImageFeaturizer
    from sparkdl_tpu.models import registry

    devices = jax.devices()
    check(len(devices) == n_devices, f"{len(devices)} devices")
    mesh = make_mesh(MeshConfig(data=n_devices))
    spec = registry.get_model_spec(model_name)

    def featurizer(with_mesh):
        return DeepImageFeaturizer(inputCol="image", outputCol="features",
                                   modelName=model_name,
                                   batchSize=batch_size, mesh=with_mesh)

    on_mesh = featurizer(mesh)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as directory:
        write_jpegs(directory, n_images, seed, around)
        rows, sharded = featurize_files(on_mesh, directory)
        _, single = featurize_files(featurizer(None), directory)
    check(sharded.shape == (n_images, spec.feature_dim)
          and np.isfinite(sharded).all(),
          f"mesh features of shape {sharded.shape}, or not finite")
    # the same bf16 program on the same rows, partitioned or not
    delta = check_features(sharded, single, "mesh vs one device")

    batch = staged_batch([r["image"] for r in rows], spec.input_size)
    run, program = compiled_program(
        on_mesh._model_function("featurize").flattened(), mesh)
    eff_batch, _ = run.bucket_params(batch_size, mesh)
    out = program(batch[:eff_batch])
    shard_devices = {s.device for s in out.addressable_shards}
    check(shard_devices == set(devices)
          and all(s.data.shape[0] == eff_batch // n_devices
                  for s in out.addressable_shards),
          f"the batch's shards sit on {shard_devices}, not one "
          f"{eff_batch // n_devices}-row shard on each device")
    # the replicated weights: live arrays the mesh program closes over
    replicated = [a for a in jax.live_arrays()
                  if a.sharding.is_fully_replicated
                  and len(a.devices()) == n_devices and a.ndim >= 2]
    on_all_devices(replicated, devices, "featurize variables")
    replicated_bytes = sum(a.nbytes for a in replicated)
    check(replicated_bytes >= 0.9 * run.weight_bytes(),
          f"{replicated_bytes} bytes replicated on all devices, the model "
          f"holds {run.weight_bytes()}")
    return {"model": model_name, "rows": n_images, "devices": n_devices,
            "max_abs_delta_vs_one_device": delta,
            "batch_shard_devices": sorted(str(d) for d in shard_devices),
            "rows_per_shard": eff_batch // n_devices,
            "replicated_arrays": len(replicated),
            "replicated_megabytes": round(replicated_bytes / 2**20, 1)}


def phase_mesh_train(model_name, batch_size, seed, n_devices):
    """One data-parallel Trainer step over the mesh against the same step
    on one device, in bfloat16 (the path users run) and in float32 at full
    matmul precision. Both are held to the step's own noise floor — the
    one-device step on the row-permuted batch (see DP_NOISE_FACTOR)."""
    import jax

    from sparkdl_tpu.core.mesh import (MeshConfig, batch_sharding,
                                       make_mesh)
    from sparkdl_tpu.models import registry
    from sparkdl_tpu.train.metrics import MetricsLogger

    devices = jax.devices()
    check(len(devices) == n_devices, f"{len(devices)} devices")
    mesh = make_mesh(MeshConfig(data=n_devices))
    (x, y), = train_batches(registry.get_model_spec(model_name), batch_size,
                            1, seed)
    order = np.random.default_rng(seed + 1).permutation(batch_size)

    def leaves(state):
        return [np.asarray(leaf, np.float64) for leaf in
                jax.tree_util.tree_leaves(jax.device_get(state.params))]

    def one_step(with_mesh, compute_dtype, rows=slice(None)):
        _, (trainer, state) = make_trainer(model_name, seed, mesh=with_mesh,
                                           compute_dtype=compute_dtype)
        start = leaves(state)
        log = MetricsLogger(sinks=[lambda record: None])  # history only
        state = trainer.fit(state, [(x[rows], y[rows])], epochs=1,
                            metrics_logger=log)
        check(int(state.step) == 1, f"step {int(state.step)}")
        return start, state, log.history[0]["loss"]

    def update_error(params, against, start):
        """|params - against| over |against - start|, L2 over every leaf."""
        num = sum(float(np.sum((p - a) ** 2))
                  for p, a in zip(params, against))
        den = sum(float(np.sum((a - p0) ** 2))
                  for a, p0 in zip(against, start))
        return (num / den) ** 0.5

    facts = {"model": model_name, "batch": batch_size, "devices": n_devices}
    failures = []
    for label, compute_dtype, precision in (("bf16", "bfloat16", None),
                                            ("float32", None, "highest")):
        with jax.default_matmul_precision(precision) if precision \
                else contextlib.nullcontext():
            start, state_one, loss_one = one_step(None, compute_dtype)
            _, state_permuted, loss_permuted = one_step(None, compute_dtype,
                                                        order)
            _, state_mesh, loss_mesh = one_step(mesh, compute_dtype)
        on_all_devices(state_mesh.params, devices,
                       f"{label} trained parameters")
        on_all_devices(state_mesh.opt_state, devices,
                       f"{label} optimizer state")
        one, mesh_params = leaves(state_one), leaves(state_mesh)
        floor = update_error(leaves(state_permuted), one, start)
        error = update_error(mesh_params, one, start)
        facts[label] = {
            "loss_one_device": loss_one, "loss_mesh": loss_mesh,
            "loss_permuted_batch": loss_permuted,
            "update_error_mesh_vs_one_device": error,
            "noise_floor_permuted_batch_vs_one_device": floor,
            "max_abs_param_delta_mesh_vs_one_device": max(
                float(np.max(np.abs(a - b)))
                for a, b in zip(one, mesh_params)),
            "leaves_outside_mlp_dp_tolerance": sum(
                not np.allclose(b, a, rtol=DP_RTOL, atol=DP_ATOL)
                for a, b in zip(one, mesh_params)),
            "leaves": len(one)}
        if not error <= max(DP_NOISE_FACTOR * floor, DP_EXACT):
            failures.append(
                f"{label}: the mesh step is {error:.4g} of the update away "
                f"from the one-device step, the noise floor is {floor:.4g}")
        if not np.isfinite(loss_mesh) or (
                precision and abs(loss_mesh - loss_one)
                > DP_FLOAT32_LOSS_RTOL * abs(loss_one)):
            failures.append(f"{label}: the mesh step's loss is {loss_mesh}, "
                            f"the one-device step's {loss_one}")

    # the batch as the mesh step sees it: the Trainer's own sharding rule
    shards = jax.device_put(x, batch_sharding(mesh, x.ndim)).addressable_shards
    shard_devices = {s.device for s in shards}
    check(shard_devices == set(devices), f"batch shards on {shard_devices}")
    facts["batch_shard_devices"] = sorted(str(d) for d in shard_devices)
    check(not failures, "; ".join(failures) + f" — {json.dumps(facts)}")
    return facts


# ---------------------------------------------------------------------------


def remove_built_artifacts():
    """A native library left on disk was built somewhere else; remove it so
    this run exercises the build from the files git tracks."""
    from sparkdl_tpu.native import loader as native_loader

    with contextlib.suppress(FileNotFoundError):
        os.remove(native_loader._library_path())


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0,
                        help="seed of the images, weights and batches")
    parser.add_argument("--chips", type=int, default=1, choices=(1, 4),
                        help="4: run only the single-process mesh path and "
                             "its one-device comparison")
    args = parser.parse_args(argv)

    device = require_tpu(args.chips)  # before anything is built
    meter = CompileMeter()
    print(json.dumps({"phase": "start", "device": device,
                      "compile_cache_dir": sparkdl_tpu._compile_cache_dir(),
                      "seed": args.seed}), flush=True)
    remove_built_artifacts()
    if args.chips == 4:
        run_phase(meter, "mesh_featurize", phase_mesh_featurize,
                  "InceptionV3", 128, 128, args.seed, 4)
        run_phase(meter, "mesh_train", phase_mesh_train,
                  "ResNet50", 64, args.seed, 4)
    else:
        staged = {}

        def featurize():
            facts, staged["batch"], staged["features"] = phase_featurize(
                "InceptionV3", 256, 128, args.seed, device["platform"])
            return facts

        run_phase(meter, "featurize", featurize)
        run_phase(meter, "serving", phase_serving, "InceptionV3",
                  staged["batch"], staged["features"], 128,
                  device["platform"])
        run_phase(meter, "train", phase_train, "ResNet50", 64, 3, args.seed,
                  device["platform"])
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
