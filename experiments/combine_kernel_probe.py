"""The expert layer's combine alone on the chip, at the four expert cells'
layer shapes (a launch of 16,384 tokens; openPangu-Ultra-MoE-718B with 16 of
its 256 experts held, the other three held whole): how far the fused kernel
agrees with XLA's tiled gathers, then milliseconds a call of each, the
kernel by token tile. Routing is drawn at random, so a held share meets
``top_k · held / experts`` pairs a token. On a TPU host, from the root of
the checkout:

    PYTHONPATH=. python experiments/combine_kernel_probe.py [tile ...]
"""

import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax

from sparkdl_tpu.models import experts, registry

TOKENS = 16384
REPEATS = 10
CELLS = {"openpangu": ("openPangu-Ultra-MoE-718B", 16),
         "lfm2": ("LFM2-8B-A1B", None),
         "mellum": ("Mellum2-12B-A2.5B-Instruct", None),
         "laguna": ("Laguna-XS.2", None)}


def operands(c, hidden, seed=0):
    """``combine``'s operands for the first round of an expert layer ``c``
    of width ``hidden`` over TOKENS tokens, the pairs placed as
    ``routed_experts`` places them."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    held = len(c.experts_held)
    scores = jax.random.uniform(keys[0], (TOKENS, c.experts))
    top, chosen = lax.top_k(scores, c.top_k)
    weights = (top / jnp.sum(top, -1, keepdims=True)).reshape(-1)
    slot_of = jnp.full((c.experts,), held, jnp.int32).at[
        jnp.asarray(c.experts_held, jnp.int32)].set(
            jnp.arange(held, dtype=jnp.int32))
    slots = slot_of[chosen].reshape(-1)
    pairs = slots.shape[0]
    order = jnp.argsort(slots, stable=True)
    local = jnp.sum(slots < held)
    place = jnp.zeros((pairs,), jnp.int32).at[order].set(
        jnp.arange(pairs, dtype=jnp.int32))
    capacity = experts.buffer_capacity(TOKENS, c)
    mine = jnp.where((place < local) & (place < capacity), weights, 0.0)
    out = jax.random.normal(keys[1], (capacity, hidden), jnp.float32)
    y = jax.random.normal(keys[2], (TOKENS, hidden), jnp.float32)
    return y, out, place, mine


def per_call_ms(fn, *args):
    """Milliseconds a call, REPEATS calls chained inside one program."""
    y, rest = args[0], args[1:]
    looped = jax.jit(lambda y, *rest: lax.fori_loop(
        0, REPEATS, lambda i, y: fn(y, *rest), y))
    jax.block_until_ready(looped(y, *rest))
    best = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(looped(y, *rest))
        best.append((time.perf_counter() - t0) / REPEATS * 1e3)
    return min(best)


def main(argv):
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("combine_kernel_probe: needs a TPU")
    asked = [int(a) for a in argv]
    for cell, (model, held) in CELLS.items():
        hidden, c = (registry.SEQUENCE_MODELS[model].hidden,
                     registry.SEQUENCE_MODELS[model].moe)
        if held is not None:
            c = dataclasses.replace(c, experts_held=tuple(range(held)))
        y, out, at, mine = operands(c, hidden)
        k = c.top_k

        def gathers(y, out, at, mine):
            return experts.gathered_combine(y, out, at, mine, k)

        want = jax.jit(gathers)(y, out, at, mine)
        line = {"cell": cell, "hidden": hidden, "top_k": k,
                "rows": out.shape[0],
                "fetched_pairs": int(jnp.sum(mine != 0)),
                "gathers_ms": per_call_ms(gathers, y, out, at, mine),
                "rule_tile": experts._combine_tile(TOKENS, hidden, k)}
        for tile in asked or [line["rule_tile"]]:
            def kernel(y, out, at, mine, tile=tile):
                return experts.fused_combine(
                    y, out, jnp.where(mine != 0, at, -1),
                    mine.reshape(TOKENS, k), tile=tile)

            try:
                got = jax.jit(kernel)(y, out, at, mine)
                line[f"tile_{tile}"] = {
                    "ms": per_call_ms(kernel, y, out, at, mine),
                    "unequal": int(jnp.sum(got != want)),
                    "max_gap": float(jnp.max(jnp.abs(got - want)))}
            except Exception as e:  # noqa: BLE001  (a tile refused)
                line[f"tile_{tile}"] = {"error": str(e)[:300]}
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
