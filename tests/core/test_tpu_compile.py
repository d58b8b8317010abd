"""The product's own programs compile for the TPU v5e at the main path's
real shapes: the model zoo's convolution units (``models/layers.py``),
``ModelFunction.resized()``'s cast-and-resize prologue, the sequence
scorer's latent attention (``models/latent_moe.py``), the second sequence
model's mixers and the expert layer held whole (``models/shortconv_moe.py``),
and the third's attention of both kinds, its expert layer and its head at a
window of 16,384 (the same module), and the fifth's gated attention of both
kinds with their own query heads and its expert layer of 256 narrow experts
(the same module).

These are the only tier-1 tests that hand the product's code to the TPU's
compiler: each program is compiled ahead of time for a DESCRIBED
``v5e:2x2`` (no chip attached, nothing runs). They also hold which
programs are XLA's own and which hold a hand-written kernel
(``tpu_custom_call``): the image programs hold none — every Pallas
candidate for them lost to XLA's twin on the chip (PERF.md §6, PR 21) — and
latent attention holds exactly the fused causal-attention kernel, which keeps
a window's float32 scores out of HBM (PERF.md §6, PR 36); the
short-convolution model's convolution holds none, and the expert layer of
either sequence model holds the grouped-product kernel twice, gate and up in
one pass and down (PERF.md §6, PR 38), in place of three ``lax.ragged_dot``;
the pre-norm stack's grouped-query attention holds the fused kernel too, at a
head width of 128 with a span or without (PR 39) and at one of 64, half a
lane group, with a key head's four query heads stacked a grid step (PR 40);
and the head of each of the three sequence models holds the fused scoring
head, which keeps a window's float32 logits out of HBM (PR 43); the
state-space mixer of the fourth holds the selective-scan kernel, which keeps
a window's states on the chip, and its attention — 20 query heads on one key
head — the fused kernel (PR 44). A
PR that ships or drops a kernel changes the assertion where it belongs.

An expert layer that holds a share of the experts holds the combine kernel
beside its two grouped products, which copies only the rows of the pairs the
chip computed in place of XLA's gathers; one held whole keeps the gathers.

The topology is described inside a fixture of this file and nowhere else:
only one process at a time may load the TPU's library, and under
pytest-xdist every worker imports every test file, so a call made at
import (or in a ``skipif``/``parametrize`` argument, or in conftest)
would take the library in the wrong worker. Compiles run in this test
process, with the persistent compilation cache off around them (an
ahead-of-time entry cannot be read back without a chip).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from sparkdl_tpu.core import ModelFunction, TensorSpec
from sparkdl_tpu.models import (attention, experts, latent_moe,
                                linear_attention, registry, scoring_head,
                                shortconv_moe, state_space)
from sparkdl_tpu.models.layers import ConvBN, SeparableConvBN


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # or the TPU compiler logs under /tmp; setdefault keeps an outside
    # choice, and the variable is only read when the library loads here
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    # the library's failure modes (absent, locked by another process) are
    # not one exception type; any of them means "cannot be described here"
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache(topo):
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


# (program, launch geometry, dtype): the Xception middle- and exit-flow
# separable convolutions (batch, H, W, C in, C out), the InceptionV3 1x1
# ConvBN units, and resized()'s prologue (batch, H, W, C, target H, target
# W) at a small, a model-sized and a typical-photo source.
_SITES = [
    ("sep", (128, 19, 19, 728, 728), "bfloat16"),
    ("sep", (8, 19, 19, 728, 728), "float32"),
    ("sep", (128, 10, 10, 1024, 1536), "bfloat16"),
    ("conv_relu", (128, 35, 35, 192, 64), "bfloat16"),
    ("conv_relu", (8, 35, 35, 288, 48), "float32"),
    ("conv_relu", (128, 17, 17, 768, 192), "bfloat16"),
    ("conv_relu", (128, 8, 8, 1280, 320), "bfloat16"),
    ("conv", (8, 8, 8, 2048, 192), "float32"),
    ("conv_relu", (8, 73, 73, 64, 80), "bfloat16"),
    ("resized", (8, 64, 64, 3, 32, 32), "float32"),
    ("resized", (8, 64, 64, 3, 32, 32), "bfloat16"),
    ("resized", (8, 224, 224, 3, 299, 299), "float32"),
    ("resized", (8, 224, 224, 3, 299, 299), "bfloat16"),
    ("resized", (8, 375, 500, 3, 299, 299), "float32"),
    ("resized", (128, 375, 500, 3, 299, 299), "bfloat16"),
]


def _program_and_arguments(site, sharding):
    """The jitted program of a site and its arguments as shapes on the
    described device (there is no device to hold an array)."""
    program, shape, dtype = site

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

    if program == "resized":
        b, h, w, c, th, tw = shape
        mf = ModelFunction(lambda vs, a: a, None,
                           TensorSpec((None, th, tw, c), dtype),
                           name="resize_only").resized((h, w))
        return (jax.jit(lambda a: mf.apply_fn(None, a)),
                (on_chip(jax.ShapeDtypeStruct((b, h, w, c), "uint8")),))
    b, h, w, cin, cout = shape
    module = (SeparableConvBN(cout) if program == "sep"
              else ConvBN(cout, (1, 1), act=program == "conv_relu"))
    x = jax.ShapeDtypeStruct((b, h, w, cin), dtype)
    variables = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)
    # with_compute_dtype's cast: variables in the compute dtype
    variables = jax.tree.map(
        lambda a: on_chip(jax.ShapeDtypeStruct(a.shape, dtype)), variables)
    return (jax.jit(lambda vs, a: module.apply(vs, a, train=False)),
            (variables, on_chip(x)))


@pytest.mark.parametrize(
    "site", _SITES,
    ids=lambda s: f"{s[0]}-{'x'.join(map(str, s[1]))}-{s[2]}")
def test_program_compiles_for_v5e(site, one_chip, no_persistent_cache):
    fn, args = _program_and_arguments(site, one_chip)
    text = fn.lower(*args).compile().as_text()
    assert ":T(" in text  # tiled layouts: the TPU's compiler made this
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("window", [4096, attention.FUSED_MAX_WINDOW])
def test_latent_attention_compiles_to_the_fused_kernel_for_v5e(
        window, one_chip, no_persistent_cache):
    """One window's latent attention at the published widths (128 heads of
    128 + 64 / 128, hidden 7,680), bfloat16 weights, shapes only: the .windows
    cell's window, and the longest the kernel takes (a head's keys and values
    stay in on-chip memory whole)."""
    c = registry.SEQUENCE_MODELS["openPangu-Ultra-MoE-718B"]

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"q_down": on_chip((c.hidden, c.q_rank), jnp.bfloat16),
         "q_norm": on_chip((c.q_rank,), jnp.float32),
         "q_up": on_chip((c.q_rank, c.heads * (c.nope + c.rope)),
                         jnp.bfloat16),
         "kv_down": on_chip((c.hidden, c.kv_rank + c.rope), jnp.bfloat16),
         "kv_norm": on_chip((c.kv_rank,), jnp.float32),
         "kv_up": on_chip((c.kv_rank, c.heads * (c.nope + c.v)),
                          jnp.bfloat16),
         "out": on_chip((c.heads * c.v, c.hidden), jnp.bfloat16)}
    x = on_chip((window, c.hidden), jnp.float32)
    text = jax.jit(lambda p, x: attention.latent_attention(
        p, x, c.heads, c.eps, attention.Rope(c.theta), c.query_block)
    ).lower(p, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "fused_causal_attention" in text
    # the blocked path's scores, 128 heads × a block of 512 queries × keys
    assert "f32[128,512," not in text


@pytest.mark.parametrize("part", ["short_conv", "grouped_attention",
                                  "routed_experts", "head"])
def test_short_convolution_model_compiles_for_v5e(part, one_chip,
                                                  no_persistent_cache):
    """LFM2-8B-A1B's parts at the published widths and the cell's launch (4
    windows of 4,096), bfloat16 weights, shapes only: the gated short
    convolution; one window's grouped-query attention, which is the fused
    kernel at a head width of 64 (32 query heads on 8 key heads, a key
    head's four stacked a grid step, operands heads first) with the keys
    grouped, not repeated, and no scores in HBM; the expert layer held
    whole, whose buffer is the 65,536 pairs, whose three grouped products are
    the grouped kernel twice (PERF.md §6, PR 38) and whose combine builds no
    tokens × buffer operand — a row for every pair: XLA's gathers, not the
    combine kernel; the head over all 65,536 ids, tied — the
    embedding as it lies is the kernel's operand — which is the fused scoring
    head over the launch's 16,384 positions and builds no logits in HBM."""
    c = registry.SEQUENCE_MODELS["LFM2-8B-A1B"]

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if part == "short_conv":
        p = {"in": on_chip((c.hidden, 3 * c.hidden)),
             "taps": on_chip((c.hidden, 3)),
             "out": on_chip((c.hidden, c.hidden))}
        fn = shortconv_moe.short_conv
        x = on_chip((4, 4096, c.hidden), jnp.float32)
    elif part == "grouped_attention":
        narrow = c.kv_heads * c.head_dim
        p = {"q": on_chip((c.hidden, c.hidden)),
             "k": on_chip((c.hidden, narrow)),
             "v": on_chip((c.hidden, narrow)),
             "q_norm": on_chip((c.head_dim,)),
             "k_norm": on_chip((c.head_dim,)),
             "out": on_chip((c.hidden, c.hidden))}

        def fn(p, x):
            return shortconv_moe.grouped_attention(p, x, c)

        x = on_chip((4096, c.hidden), jnp.float32)
    elif part == "head":
        p = {"embed": on_chip((c.vocab, c.hidden)),
             "final_norm": on_chip((c.hidden,))}

        def fn(p, x):
            return scoring_head.score_head(
                dict(p, head=p["embed"]), x, jnp.zeros((4, 4096), jnp.int32),
                c.eps)

        x = on_chip((4, 4096, c.hidden), jnp.float32)
    else:
        n, width = c.moe.experts, c.moe.expert_width
        p = {"router": on_chip((c.hidden, n)),
             "expert_bias": on_chip((n,)),
             "experts": {
                 "gate": on_chip((n, c.hidden, width)),
                 "up": on_chip((n, c.hidden, width)),
                 "down": on_chip((n, width, c.hidden))}}

        def fn(p, x):
            return experts.routed_experts(p, x, c.moe)

        x = on_chip((4 * 4096, c.hidden), jnp.float32)
    text = jax.jit(fn).lower(p, x).compile().as_text()
    assert ":T(" in text  # tiled layouts: the TPU's compiler made this
    assert ("fused_causal_attention" in text) == (part == "grouped_attention")
    if part == "routed_experts":
        assert experts.buffer_capacity(4 * 4096, c.moe) == 65536
        # gate and up as one kernel, down as another, and no product of
        # XLA's; a row for every pair: the combine's gathers, not its kernel
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        assert "grouped_product" in text and "ragged-dot" not in text
        # gate's and up's float32 results never reach HBM
        assert "f32[65536,1792]" not in text
        assert "[16384,65536]" not in text and "[65536,16384]" not in text
    elif part == "grouped_attention":
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        # the kernel reads a key head's 4,096 keys once for its four query
        # heads: no copy of the keys to 32 heads, and none of the blocked
        # path's scores, 8 key heads × their 4 query heads × a block of
        # queries
        assert "bf16[8,4096,64]" in text and "[32,4096,64]" not in text
        assert "f32[8,4,512," not in text
    elif part == "head":
        _holds_the_fused_head_and_no_logits(text, c.vocab)
    else:
        assert "tpu_custom_call" not in text


def _holds_the_fused_head_and_no_logits(text, rows):
    """One kernel call, the fused scoring head's, and no buffer of positions
    × the head's rows, float32 or other — nor a transposed copy of the head,
    which the kernel reads as it lies."""
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "fused_scoring_head" in text
    assert f",{rows}]" not in text


def test_a_share_of_the_vocabulary_compiles_to_the_fused_head_for_v5e(
        one_chip, no_persistent_cache):
    """openPangu-Ultra-MoE-718B's head as the .windows cell holds it (19,200
    of 153,600 rows = 2**8 × 75, hidden 7,680, 4 windows of 4,096): the
    fused scoring head at blocks this contraction leaves room for."""
    c = registry.SEQUENCE_MODELS["openPangu-Ultra-MoE-718B"]
    rows = 19200
    assert scoring_head._head_blocks(4096, c.hidden, rows, jnp.bfloat16)

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"embed": on_chip((rows, c.hidden)), "head": on_chip((rows, c.hidden)),
         "final_norm": on_chip((c.hidden,))}
    x = on_chip((4, 4096, c.hidden), jnp.float32)
    text = jax.jit(lambda p, x: scoring_head.score_head(
        p, x, jnp.zeros((4, 4096), jnp.int32), c.eps)
        ).lower(p, x).compile().as_text()
    _holds_the_fused_head_and_no_logits(text, rows)


def test_a_share_of_the_experts_compiles_to_the_grouped_kernel_for_v5e(
        one_chip, no_persistent_cache):
    """openPangu-Ultra-MoE-718B's expert layer as the .windows cell holds it
    (16 of 256 experts, a buffer of 16,384 rows × 7,680 of which routing
    fills about half, rounds under a ``fori_loop`` with traced group sizes):
    the same two kernel calls inside the loop, their column tiles cut to the
    blocks' budget of on-chip memory, and no product of XLA's; and the
    combine kernel beside them, which fetches only the rows of the pairs
    this chip computed, in place of XLA's gathers."""
    import dataclasses

    published = registry.SEQUENCE_MODELS["openPangu-Ultra-MoE-718B"]
    c = dataclasses.replace(published, moe=dataclasses.replace(
        published.moe, experts_held=tuple(range(16))))

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    p = {"router": on_chip((c.hidden, c.moe.experts)),
         "experts": {"gate": on_chip((16, c.hidden, c.moe.expert_width)),
                     "up": on_chip((16, c.hidden, c.moe.expert_width)),
                     "down": on_chip((16, c.moe.expert_width, c.hidden))}}
    x = on_chip((4 * 4096, c.hidden), jnp.float32)
    text = jax.jit(lambda p, x: experts.routed_experts(p, x, c.moe)
                   ).lower(p, x).compile().as_text()
    assert experts.buffer_capacity(4 * 4096, c.moe) == 16384
    assert text.count('custom_call_target="tpu_custom_call"') == 3
    assert "grouped_product" in text and "ragged-dot" not in text
    assert "fused_combine" in text
    assert "f32[16384,2048]" not in text


@pytest.mark.parametrize("part", ["sliding_attention", "full_attention",
                                  "routed_experts", "head"])
def test_span_model_compiles_for_v5e(part, one_chip, no_persistent_cache):
    """Mellum2-12B-A2.5B-Instruct's parts at the published widths and the
    cell's launch (one window of 16,384), bfloat16 weights, shapes only: one
    window's grouped-query attention of each kind, which is the fused kernel
    on grouped keys (32 query heads on 4 key heads of 128) — within its
    budget of on-chip memory with a head's 16,384 keys and values resident —
    and builds no scores in HBM; the expert layer held whole (64 experts, a
    buffer of the 131,072 pairs, the grouped kernel twice at 2,304 × 896 and
    the combine's gathers);
    the head over 98,304 ids, which is the fused scoring head and builds none
    of the window's 6.4 GB of float32 logits, whole or in blocks."""
    c = registry.SEQUENCE_MODELS["Mellum2-12B-A2.5B-Instruct"]
    window = 16384
    assert window == attention.FUSED_MAX_WINDOW

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if part.endswith("attention"):
        wide, narrow = c.heads * c.head_dim, c.kv_heads * c.head_dim
        p = {"q": on_chip((c.hidden, wide)), "k": on_chip((c.hidden, narrow)),
             "v": on_chip((c.hidden, narrow)),
             "out": on_chip((wide, c.hidden))}

        def fn(p, x):
            return shortconv_moe.grouped_attention(p, x, c, part)

        x = on_chip((window, c.hidden), jnp.float32)
    elif part == "routed_experts":
        n, width = c.moe.experts, c.moe.expert_width
        p = {"router": on_chip((c.hidden, n)),
             "experts": {
                 "gate": on_chip((n, c.hidden, width)),
                 "up": on_chip((n, c.hidden, width)),
                 "down": on_chip((n, width, c.hidden))}}

        def fn(p, x):
            return experts.routed_experts(p, x, c.moe)

        x = on_chip((window, c.hidden), jnp.float32)
    else:
        p = {"embed": on_chip((c.vocab, c.hidden)),
             "head": on_chip((c.vocab, c.hidden)),
             "final_norm": on_chip((c.hidden,))}

        def fn(p, x):
            return scoring_head.score_head(
                p, x, jnp.zeros((1, window), jnp.int32), c.eps)

        x = on_chip((1, window, c.hidden), jnp.float32)
    text = jax.jit(fn).lower(p, x).compile().as_text()
    assert ":T(" in text  # tiled layouts: the TPU's compiler made this
    if part.endswith("attention"):
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert "fused_causal_attention" in text
        # no scores of a key head's 8 query heads × a block of queries
        assert "f32[4,8,512," not in text
    elif part == "routed_experts":
        assert experts.buffer_capacity(window, c.moe) == 131072
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        assert "grouped_product" in text and "ragged-dot" not in text
        assert "f32[131072,896]" not in text
    else:
        _holds_the_fused_head_and_no_logits(text, c.vocab)


@pytest.mark.parametrize("part", ["state_space", "attention", "head"])
def test_state_space_model_compiles_for_v5e(part, one_chip,
                                            no_persistent_cache):
    """AI21-Jamba2-3B's parts at the published widths and the cell's launch
    (one window of 16,384), bfloat16 weights, shapes only: the state-space
    mixer, which holds the selective-scan kernel — 128 blocks of 128
    positions, the state of 5,120 × 16 values on the chip throughout — and
    builds no window's states (5.4 GB) nor a block's coefficients in HBM;
    attention without a rotary, 20 query heads on one key head of 128, which
    is the fused kernel; the tied head over 65,536 ids at a hidden of 2,560,
    which is the fused scoring head."""
    c = registry.SEQUENCE_MODELS["AI21-Jamba2-3B"]
    window = 16384
    assert window % state_space.SCAN_TIME_BLOCK == 0
    assert (c.d_inner, c.d_state, c.dt_rank, c.d_conv) == (5120, 16, 160, 4)

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if part == "state_space":
        p = {"in": on_chip((c.hidden, 2 * c.d_inner)),
             "taps": on_chip((c.d_inner, c.d_conv)),
             "conv_bias": on_chip((c.d_inner,)),
             "x": on_chip((c.d_inner, c.dt_rank + 2 * c.d_state)),
             "dt_norm": on_chip((c.dt_rank,)),
             "b_norm": on_chip((c.d_state,)), "c_norm": on_chip((c.d_state,)),
             "dt": on_chip((c.dt_rank, c.d_inner)),
             "dt_bias": on_chip((c.d_inner,)),
             "a_log": on_chip((c.d_inner, c.d_state)),
             "d": on_chip((c.d_inner,)),
             "out": on_chip((c.d_inner, c.hidden))}

        def fn(p, x):
            return state_space.state_space(p, x, c)

        x = on_chip((1, window, c.hidden), jnp.float32)
    elif part == "attention":
        wide, narrow = c.heads * c.head_dim, c.kv_heads * c.head_dim
        assert (wide, narrow) == (2560, 128)
        p = {"q": on_chip((c.hidden, wide)), "k": on_chip((c.hidden, narrow)),
             "v": on_chip((c.hidden, narrow)),
             "out": on_chip((wide, c.hidden))}

        def fn(p, x):
            return shortconv_moe.grouped_attention(p, x, c)

        x = on_chip((window, c.hidden), jnp.float32)
    else:
        embed = on_chip((c.vocab, c.hidden))
        p = {"embed": embed, "head": embed,
             "final_norm": on_chip((c.hidden,))}

        def fn(p, x):
            return scoring_head.score_head(
                p, x, jnp.zeros((1, window), jnp.int32), c.eps)

        x = on_chip((1, window, c.hidden), jnp.float32)
    text = jax.jit(fn).lower(p, x).compile().as_text()
    assert ":T(" in text  # tiled layouts: the TPU's compiler made this
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    if part == "state_space":
        assert "selective_scan" in text
        assert "f32[16384,16,5120]" not in text
        assert f"f32[{state_space.PLAIN_BLOCK},16,5120]" not in text
    elif part == "attention":
        assert "fused_causal_attention" in text
        # no scores of the key head's 20 query heads × a block of queries
        assert "f32[1,20,512," not in text
    else:
        _holds_the_fused_head_and_no_logits(text, c.vocab)


@pytest.mark.parametrize("part", ["full_attention", "sliding_attention",
                                  "routed_experts"])
def test_gated_attention_and_narrow_experts_compile_for_v5e(
        part, one_chip, no_persistent_cache):
    """Laguna-XS.2's parts at the published widths and the cell's launch (4
    windows of 4,096), bfloat16 weights, shapes only: one window's gated
    attention of each kind — 48 query heads (a full layer, half of each head
    turned) or 64 (a sliding one, span 512) on 8 key heads of 128, the query
    heads read off the weights — which is the fused kernel on grouped keys
    and builds no scores in HBM; the expert layer of 256 experts of 512 held
    whole, a buffer of the 131,072 pairs, the grouped kernel twice and the
    combine's gathers."""
    c = registry.SEQUENCE_MODELS["Laguna-XS.2"]
    window = 4096

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if part.endswith("attention"):
        heads = 48 if part == "full_attention" else 64
        wide, narrow = heads * c.head_dim, c.kv_heads * c.head_dim
        p = {"q": on_chip((c.hidden, wide)), "k": on_chip((c.hidden, narrow)),
             "v": on_chip((c.hidden, narrow)),
             "gate": on_chip((c.hidden, heads)),
             "out": on_chip((wide, c.hidden))}

        def fn(p, x):
            return shortconv_moe.grouped_attention(p, x, c, part)

        x = on_chip((window, c.hidden), jnp.float32)
    else:
        n, width = c.moe.experts, c.moe.expert_width
        p = {"router": on_chip((c.hidden, n)),
             "experts": {
                 "gate": on_chip((n, c.hidden, width)),
                 "up": on_chip((n, c.hidden, width)),
                 "down": on_chip((n, width, c.hidden))}}

        def fn(p, x):
            return experts.routed_experts(p, x, c.moe)

        x = on_chip((4 * window, c.hidden), jnp.float32)
    text = jax.jit(fn).lower(p, x).compile().as_text()
    assert ":T(" in text  # tiled layouts: the TPU's compiler made this
    if part.endswith("attention"):
        assert text.count('custom_call_target="tpu_custom_call"') == 1
        assert "fused_causal_attention" in text
        # no scores of a key head's query heads × a block of queries
        assert f"f32[8,{heads // 8},512," not in text
    else:
        assert experts.buffer_capacity(4 * window, c.moe) == 131072
        assert text.count('custom_call_target="tpu_custom_call"') == 2
        assert "grouped_product" in text and "ragged-dot" not in text
        assert "f32[131072,512]" not in text


@pytest.mark.parametrize("model,held", [
    ("openPangu-Ultra-MoE-718B", 16), ("LFM2-8B-A1B", None),
    ("Mellum2-12B-A2.5B-Instruct", None), ("Laguna-XS.2", None)])
def test_the_combine_compiles_to_its_kernel_for_v5e(model, held, one_chip,
                                                    no_persistent_cache):
    """The combine alone at each expert cell's layer (a launch of 16,384
    tokens, the grouped products' float32 output of ``buffer_capacity``
    rows): where a share of the experts is held, the combine kernel, which
    gathers no row with XLA's gathers — a row of ``out`` reaches the chip
    only by the kernel's own DMA; where a layer is held whole, a row for
    every pair, XLA's gathers and no kernel."""
    import dataclasses

    c = registry.SEQUENCE_MODELS[model]
    if held is not None:
        c = dataclasses.replace(c, moe=dataclasses.replace(
            c.moe, experts_held=tuple(range(held))))
    tokens = 4 * 4096
    rows = experts.buffer_capacity(tokens, c.moe)
    assert (rows < tokens * c.moe.top_k) == (held is not None)

    def on_chip(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(lambda *a: experts.combine(*a, c.moe.top_k)).lower(
        on_chip((tokens, c.hidden)), on_chip((rows, c.hidden)),
        on_chip((tokens * c.moe.top_k,), jnp.int32),
        on_chip((tokens * c.moe.top_k,))).compile().as_text()
    kernel = held is not None
    assert text.count('custom_call_target="tpu_custom_call"') == kernel
    assert (" gather(" in text) != kernel
    if kernel:
        assert "fused_combine" in text


@pytest.mark.parametrize("part", ["linear_attention", "latent_attention"])
def test_ling_model_compiles_for_v5e(part, one_chip, no_persistent_cache):
    """Ling-3.0-flash's two mixers at the published widths and the cell's
    launch (one window of 16,384), bfloat16 weights, shapes only: linear
    attention, whose chunked form is the delta-rule kernel alone — a grid of
    32 heads × 64 steps of 4 chunks that makes each chunk's terms on the
    chip, the 128 × 128 state there throughout; no triangular solve is left
    to XLA, and the mixer's bytes accessed are under 25 GB (37.5 GB while
    XLA made the terms); and latent attention with its queries straight
    from the input, 32 heads of 128 + 64, which is the fused attention
    kernel."""
    c = registry.SEQUENCE_MODELS["Ling-3.0-flash"]
    window, wide = 16384, c.heads * c.head_dim

    def on_chip(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if part == "linear_attention":
        p = {**{name: on_chip((c.hidden, wide)) for name in "qkvf"},
             **{f"{name}_taps": on_chip((wide, 4)) for name in "qkv"},
             "dt_bias": on_chip((wide,)), "A_log": on_chip((c.heads,)),
             "beta": on_chip((c.hidden, c.heads)),
             "gate": on_chip((c.hidden, c.heads)),
             "o_norm": on_chip((c.head_dim,)),
             "out": on_chip((wide, c.hidden))}

        def fn(p, x):
            return linear_attention.kda(p, x, c.eps, c.kda_lower_bound)

        x = on_chip((1, window, c.hidden), jnp.float32)
    else:
        p = {"q": on_chip((c.hidden, c.heads * 192)),
             "kv_down": on_chip((c.hidden, 512 + 64)),
             "kv_norm": on_chip((512,)),
             "kv_up": on_chip((512, c.heads * 256)),
             "gate": on_chip((c.hidden, c.heads)),
             "out": on_chip((wide, c.hidden))}

        def fn(p, x):
            return attention.latent_attention(
                p, x, c.heads, c.eps, dict(c.rope)["latent_attention"],
                c.query_block)

        x = on_chip((window, c.hidden), jnp.float32)
    compiled = jax.jit(fn).lower(p, x).compile()
    text = compiled.as_text()
    assert ":T(" in text  # tiled layouts: the TPU's compiler made this
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert ("fused_delta_rule" in text) == (part == "linear_attention")
    if part == "linear_attention":
        assert "triangular" not in text.lower()
        assert compiled.cost_analysis()["bytes accessed"] < 25e9
