"""Pallas fused TPU kernels behind an accept-if-faster autotune (ISSUE 20).

The dormant probes validated the kernel shapes (``experiments/
pallas_probe.py``: the ``fma9`` VPU ceiling, the ``dw2d`` row-major
layout, the ``sep2d`` one-VMEM-residency fusion); this module is their
production port plus the machinery that makes shipping them SAFE:

- **Fused kernels** — :func:`sep2d` (relu? → 3×3 SAME depthwise → 1×1
  pointwise matmul → folded-BN affine, one VMEM residency, no HBM
  round trip between dw and pw — the Xception ``SeparableConvBN``
  body), :func:`pw1x1` (1×1 conv as an MXU matmul with the BN affine
  and optional relu fused as the epilogue — the InceptionV3 ``ConvBN``
  1×1 stride-1 sites), and :func:`preproc_resize` (uint8 → float cast
  + bilinear resize as two interpolation-matrix matmuls per channel
  plane — the fused-preprocess prologue, one Pallas launch instead of
  N XLA ops). Each has an XLA twin (:func:`xla_sep2d` …) that
  reproduces the exact op order of the Flax layer it would replace.

- **Accept-if-faster autotune** — models never call the kernels
  directly; they call ``route_*`` (via the structural opt-in in
  ``models/layers.py``), and a route only returns the fused
  computation when a per-(kernel, model-family, shape, dtype) verdict
  says the Pallas candidate beat its XLA twin by ≥5% at that exact
  shape AND stayed inside the numeric contract (fp32 exact, bf16
  within :data:`BF16_TOLERANCE`). Verdicts are produced by
  :func:`ensure_autotuned` — hooked into ``ModelFunction``'s
  first-launch-of-a-shape path, so shootouts run at the deployment's
  actual bucket rungs, before the shape's first trace — and persist
  beside the compile cache (``$JAX_COMPILATION_CACHE_DIR/
  sparkdl_kernel_verdicts.json``, atomic replace, versioned): a losing
  kernel is never re-auditioned every boot, but because the batch
  dimension is part of the key, a bucket-ladder retune (new rungs →
  new keys) re-auditions automatically. A losing or numerically-off
  kernel NEVER ships — which is what makes defaulting
  ``EngineConfig.pallas_kernels`` to ``"autotune"`` safe: on a backend
  without Mosaic lowering (CPU tests) every audition records a clean
  rejection and the routed program is byte-identical to the XLA one.

Gating: ``EngineConfig.pallas_kernels`` — ``"off"`` (this module is
never imported; subprocess-pinned), ``"autotune"`` (default),
``"force"`` (route every feasible site, no shootout — tests drive it
with :data:`INTERPRET` to exercise kernel numerics on CPU).

Telemetry: ``sparkdl.kernel.autotune_s`` histogram per shootout,
``sparkdl.kernel.adopted``/``rejected`` counters, and
``sparkdl.kernel.audition_error`` for an audition that raised where
Mosaic lowers (never a clean rejection, never persisted). docs/PERF.md "Fused
kernels & AOT warmup" is the operator story; the ``kernel-gate``
analyzer rule keeps raw ``pallas_call``/kernel entry points from
bypassing this registry anywhere else in the tree.
"""

from __future__ import annotations

import functools
import json
import logging
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from sparkdl_tpu.core import telemetry

logger = logging.getLogger(__name__)

#: bf16 numeric contract: max |pallas - xla| per element a candidate may
#: show against its XLA twin and still be adopted (the same 0.05 bound
#: docs/PERF.md guarantees for the bf16 inference path as a whole).
#: fp32 candidates must match exactly.
BF16_TOLERANCE = 0.05
#: Accept-if-faster bar: adopted only when pallas_s <= 0.95 * xla_s.
ADOPT_SPEEDUP = 0.95
#: Run every pallas_call in interpreter mode (CPU-executable, slow) —
#: how the test suite exercises kernel numerics and the routing plumbing
#: without a TPU. Flipping it changes the verdict backend tag, so
#: interpreter verdicts never leak into real-hardware stores.
INTERPRET = False

#: Raw kernel builders. Calling these anywhere outside this module
#: bypasses the accept-if-faster gate — flagged by the ``kernel-gate``
#: analyzer rule (docs/ANALYSIS.md); production code goes through the
#: ``route_*`` entry points.
RAW_KERNEL_ENTRY_POINTS = frozenset({"sep2d", "pw1x1", "preproc_resize"})

#: VMEM sizing caps for one grid step's blocks (conservative: Mosaic
#: double-buffers in/out blocks, and the pw weight block is resident
#: across the whole grid).
_BLOCK_LIMIT_BYTES = 1536 * 1024
_WEIGHT_LIMIT_BYTES = 4 * 1024 * 1024

_VERDICT_STORE_BASENAME = "sparkdl_kernel_verdicts.json"
VERDICT_STORE_VERSION = 2


# ---------------------------------------------------------------------------
# Sites and verdicts
# ---------------------------------------------------------------------------


@dataclass(frozen=True, order=True)
class Site:
    """One autotunable kernel site: WHAT would run WHERE.

    ``shape`` carries the full launch geometry including the batch
    dimension — bucket-ladder rungs are therefore distinct sites, which
    is both how the shootout times the deployment's real shapes and how
    a ladder retune re-auditions kernels (new rungs → new keys) without
    any explicit invalidation."""

    kernel: str
    family: str
    shape: Tuple[int, ...]
    dtype: str


def _backend_tag() -> str:
    return "interpret" if INTERPRET else jax.default_backend()


def _site_key(site: Site) -> str:
    return "|".join((site.kernel, site.family,
                     "x".join(str(d) for d in site.shape), site.dtype,
                     _backend_tag()))


def verdict_store_path() -> Optional[str]:
    """Verdict persistence file, beside the persistent compilation cache
    when ``$JAX_COMPILATION_CACHE_DIR`` names it — the same placement as
    the learned bucket ladders: a warm process reloads the shootout
    outcomes together with the compiled programs they selected. None
    otherwise (verdicts stay in-process)."""
    from sparkdl_tpu import _sidecar_store_dir

    store_dir = _sidecar_store_dir()
    if store_dir is None:
        return None
    return os.path.join(store_dir, _VERDICT_STORE_BASENAME)


_verdicts: Dict[str, Dict[str, Any]] = {}
_verdicts_loaded = False
_verdict_lock = threading.Lock()
# per-site single-flight: concurrent callers of the SAME site wait on
# the owner's event (no lock held across the shootout's device work)
_inflight: Dict[str, threading.Event] = {}


def _read_store() -> Dict[str, Dict[str, Any]]:
    """Parse the store file. A corrupt file or a stale ``version`` is
    DISCARDED, never trusted — the worst case is re-auditioning, which
    is exactly what a format change wants."""
    path = verdict_store_path()
    if path is None:
        return {}
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(doc, dict) \
            or doc.get("version") != VERDICT_STORE_VERSION:
        return {}
    stored = doc.get("verdicts")
    if not isinstance(stored, dict):
        return {}
    return {key: verdict for key, verdict in stored.items()
            if isinstance(key, str) and isinstance(verdict, dict)
            and isinstance(verdict.get("adopted"), bool)}


def _ensure_loaded() -> None:
    """Populate the in-memory verdict map from the store file once per
    process (file I/O outside the lock; a racing double-read merges
    identically via setdefault)."""
    global _verdicts_loaded
    if _verdicts_loaded:
        return
    stored = _read_store()
    with _verdict_lock:
        if _verdicts_loaded:
            return
        for key, verdict in stored.items():
            _verdicts.setdefault(key, verdict)
        _verdicts_loaded = True


def _persist_verdict(key: str, verdict: Dict[str, Any]) -> None:
    """Merge one verdict into the store file (tmp + ``os.replace``
    atomic swap; concurrent writers race whole-file, last wins — the
    store is a cache, not a source of truth)."""
    path = verdict_store_path()
    if path is None:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        doc: Dict[str, Any] = {"version": VERDICT_STORE_VERSION,
                               "verdicts": {}}
        try:
            with open(path) as f:
                loaded = json.load(f)
            if isinstance(loaded, dict) \
                    and loaded.get("version") == VERDICT_STORE_VERSION \
                    and isinstance(loaded.get("verdicts"), dict):
                doc = loaded
        except (OSError, ValueError):
            pass
        doc.setdefault("verdicts", {})[key] = verdict
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
    except OSError as e:  # persistence is best-effort
        logger.warning("could not persist kernel verdict to %s: %s",
                       path, e)


def verdict_for(site: Site) -> Optional[Dict[str, Any]]:
    """The stored shootout outcome for ``site`` (None = never
    auditioned on this backend)."""
    _ensure_loaded()
    with _verdict_lock:
        return _verdicts.get(_site_key(site))


def verdicts_snapshot() -> Dict[str, Dict[str, Any]]:
    """Every verdict this process knows (bench's per-rung report)."""
    _ensure_loaded()
    with _verdict_lock:
        return {k: dict(v) for k, v in _verdicts.items()}


def reset() -> None:
    """Forget every in-memory verdict (test isolation; the store file,
    if any, is re-read on next use)."""
    global _verdicts_loaded
    with _verdict_lock:
        _verdicts.clear()
        _verdicts_loaded = False


# ---------------------------------------------------------------------------
# Mode + routing decisions
# ---------------------------------------------------------------------------


def kernel_mode() -> str:
    """``EngineConfig.pallas_kernels`` without requiring the engine
    (core stays importable standalone → ``"off"``)."""
    try:
        from sparkdl_tpu.engine.dataframe import EngineConfig
    except Exception:  # sparkdl: allow(broad-retry): layering probe — any
        # import failure means "no engine configured", i.e. kernels off
        return "off"
    return getattr(EngineConfig, "pallas_kernels", "off")


_collect = threading.local()


def _collecting() -> Optional[set]:
    return getattr(_collect, "sites", None)


def _decide(site: Site, feasible: bool) -> bool:
    """Route-time verdict lookup: True = run the Pallas candidate.

    Under a collection scope (:func:`ensure_autotuned`'s abstract
    pass), the site is recorded and the XLA path chosen — collection
    must never launch device work. ``"force"`` routes every feasible
    site (tests); ``"autotune"`` requires an adopted verdict."""
    sites = _collecting()
    if sites is not None:
        sites.add(site)
        return False
    mode = kernel_mode()
    if mode == "force":
        return feasible
    if mode != "autotune" or not feasible:
        return False
    verdict = verdict_for(site)
    return bool(verdict is not None and verdict.get("adopted"))


def ensure_autotuned(fn, x, model: str = "model") -> None:
    """Audition every kernel site ``fn(x)`` would route through, BEFORE
    its first real trace.

    Called by ``ModelFunction._build_jitted``'s first-launch-of-a-shape
    wrapper: an abstract pass (``jax.eval_shape`` under a collection
    scope) discovers the sites at zero device cost, then each missing
    verdict runs one shootout. By the time the real trace happens the
    routes resolve against settled verdicts — a request never blocks on
    a shootout mid-trace."""
    if kernel_mode() != "autotune":
        return
    sites: set = set()
    prev = _collecting()
    _collect.sites = sites
    try:
        jax.eval_shape(fn, x)
    except Exception as e:  # sparkdl: allow(broad-retry): collection is
        # best-effort discovery — a model that cannot abstractly
        # evaluate simply gets no kernels, never a broken launch. On the
        # TPU that silence would hide every kernel of the model, so there
        # it is a WARNING.
        logger.log(
            logging.WARNING if jax.default_backend() == "tpu"
            else logging.DEBUG,
            "kernel site collection failed for %s: %s", model, e)
    finally:
        _collect.sites = prev
    for site in sorted(sites):
        ensure_verdict(site)


# ---------------------------------------------------------------------------
# Geometry: layout + block sizing (shared by routes and raw builders)
# ---------------------------------------------------------------------------


def _sublane(dtype) -> Optional[int]:
    dtype = jnp.dtype(dtype)
    if dtype == jnp.float32:
        return 8
    if dtype == jnp.bfloat16:
        return 16
    return None


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sep2d_geometry(b: int, h: int, w: int, cin: int, cout: int,
                    dtype) -> Optional[Tuple[int, int]]:
    """(P_PAD, BT) for the row-major sep2d layout, or None when the
    site cannot fit the VMEM block budget (route falls back to XLA)."""
    sub = _sublane(dtype)
    if sub is None or h < 3 or w < 3 or b < 1:
        return None
    p_pad = _round_up(h * w, sub)
    item = jnp.dtype(dtype).itemsize
    if cin * cout * item > _WEIGHT_LIMIT_BYTES:
        return None
    row_bytes = p_pad * max(cin, cout) * item
    if row_bytes > _BLOCK_LIMIT_BYTES:
        return None
    cap = _BLOCK_LIMIT_BYTES // row_bytes
    bt = 1
    for d in range(1, min(b, cap) + 1):
        if b % d == 0:
            bt = d
    return p_pad, bt


def _pw1x1_geometry(n: int, cin: int, cout: int,
                    dtype) -> Optional[Tuple[int, int]]:
    """(rows per block, padded row count) for the flattened 1×1 matmul
    layout, or None when infeasible."""
    sub = _sublane(dtype)
    if sub is None or n < 1:
        return None
    item = jnp.dtype(dtype).itemsize
    if cin * cout * item > _WEIGHT_LIMIT_BYTES:
        return None
    r_blk = None
    for r in (1024, 512, 256, 128, 64, 32, 16, 8):
        if r % sub:
            continue
        if r * max(cin, cout) * item <= _BLOCK_LIMIT_BYTES:
            r_blk = r
            break
    if r_blk is None:
        return None
    return r_blk, _round_up(n, r_blk)


def _preproc_geometry(h: int, w: int, th: int, tw: int) -> bool:
    return (h * w * 4 <= _BLOCK_LIMIT_BYTES
            and th * tw * 4 <= _BLOCK_LIMIT_BYTES
            and max(th * h, tw * w) * 4 <= _BLOCK_LIMIT_BYTES)


def _pad_rows(x, p_pad: int):
    """(B, H, W, C) → (B·P_PAD, C): image positions row-major, each
    image zero-padded to P_PAD rows so every BT block is
    sublane-aligned (device-side: reshape + pad fuse into the
    surrounding program)."""
    b, h, w, c = x.shape
    flat = x.reshape(b, h * w, c)
    flat = jnp.pad(flat, ((0, 0), (0, p_pad - h * w), (0, 0)))
    return flat.reshape(b * p_pad, c)


def _unpad_rows(y, b: int, h: int, w: int, cout: int, p_pad: int):
    return y.reshape(b, p_pad, cout)[:, :h * w].reshape(b, h, w, cout)


# ---------------------------------------------------------------------------
# The kernels (production ports of experiments/pallas_probe.py)
# ---------------------------------------------------------------------------


def _row_coords(r: int, w: int, p_pad: int):
    # 2D iota only (Mosaic rejects 1D); (r, 1) broadcasts against (r, C)
    rows = jax.lax.broadcasted_iota(jnp.int32, (r, 1), 0)
    p = rows % p_pad
    return p // w, p % w  # h, w per row (p >= H*W: dead pad rows)


def _dw_rows(x, k_ref, h: int, w: int, p_pad: int, relu_in: bool):
    """3×3 SAME depthwise on a (R, C) block holding BT images of (h, w)
    positions row-major. One combined row shift per tap (w·dy + dx):
    row-major positions make the (dy, dx) neighbor a fixed row offset;
    masks computed from the row index kill rows whose source crossed an
    image/H/W edge (including the dead pad rows — any p ≥ h·w source
    reaching a live dest is edge-masked). Keeps live VMEM to ~3 tiles."""
    if relu_in:
        x = jnp.maximum(x, 0)
    rows = x.shape[0]
    hh, ww = _row_coords(rows, w, p_pad)
    zero = jnp.zeros((), x.dtype)

    def shift_rows(a, s):
        # a[r] <- a[r+s], zero-filled (Mosaic bf16 has no rotate; static
        # slice+concat lowers to sublane relayout copies)
        if s == 0:
            return a
        pad = jnp.zeros((abs(s), a.shape[1]), a.dtype)
        if s > 0:
            return jnp.concatenate([a[s:], pad], axis=0)
        return jnp.concatenate([pad, a[:s]], axis=0)

    acc = None
    for j, (dy, dx) in enumerate(
            (dy, dx) for dy in (-1, 0, 1) for dx in (-1, 0, 1)):
        valid = ((hh + dy >= 0) & (hh + dy <= h - 1)
                 & (ww + dx >= 0) & (ww + dx <= w - 1))
        t = jnp.where(valid, shift_rows(x, w * dy + dx),
                      zero) * k_ref[j:j + 1, :]
        acc = t if acc is None else acc + t
    return acc


def _sep2d_kernel(x_ref, k_ref, pw_ref, sc_ref, sh_ref, o_ref, *,
                  h: int, w: int, p_pad: int, relu_in: bool):
    t = _dw_rows(x_ref[:], k_ref, h, w, p_pad, relu_in)
    y = jnp.dot(t, pw_ref[:], preferred_element_type=jnp.float32)
    y = y * sc_ref[0:1, :] + sh_ref[0:1, :]
    o_ref[:] = y.astype(o_ref.dtype)


def sep2d(x, dw9, pw, scale, shift, *, relu_in: bool = False,
          interpret: Optional[bool] = None):
    """Fused relu? → 3×3 SAME stride-1 depthwise → 1×1 pointwise → BN
    affine: ``(B, H, W, Cin) → (B, H, W, Cout)`` in ONE VMEM residency
    (the depthwise result feeds the pointwise MXU matmul without an HBM
    round trip — the ``sep2d`` probe shape productionized).

    ``dw9`` is the depthwise kernel as (9, Cin) tap-major; ``pw``
    (Cin, Cout); ``scale``/``shift`` the folded BN affine as (1, Cout)
    float32. Raw entry point — production code routes through
    :func:`route_sep2d` (``kernel-gate`` enforces this)."""
    b, h, w, cin = x.shape
    cout = pw.shape[-1]
    geom = _sep2d_geometry(b, h, w, cin, cout, x.dtype)
    if geom is None:
        raise ValueError(
            f"sep2d site b{b} {h}x{w}x{cin}->{cout} {jnp.dtype(x.dtype)} "
            "exceeds the VMEM block budget")
    p_pad, bt = geom
    r = bt * p_pad
    grid = b // bt
    x2 = _pad_rows(x, p_pad)
    p = h * w
    kernel = functools.partial(_sep2d_kernel, h=h, w=w, p_pad=p_pad,
                               relu_in=relu_in)
    y2 = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((r, cin), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((9, cin), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((cin, cout), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cout), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cout), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r, cout), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b * p_pad, cout), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=b * (p * cin * 9 * 2 + p * cin * cout * 2),
            bytes_accessed=(x2.size + b * p_pad * cout)
            * jnp.dtype(x.dtype).itemsize,
            transcendentals=0,
        ),
        interpret=INTERPRET if interpret is None else interpret,
    )(x2, dw9, pw, scale, shift)
    return _unpad_rows(y2, b, h, w, cout, p_pad)


def _pw1x1_kernel(x_ref, w_ref, sc_ref, sh_ref, o_ref, *, relu: bool):
    y = jnp.dot(x_ref[:], w_ref[:], preferred_element_type=jnp.float32)
    y = y * sc_ref[0:1, :] + sh_ref[0:1, :]
    if relu:
        y = jnp.maximum(y, 0)
    o_ref[:] = y.astype(o_ref.dtype)


def pw1x1(x, w2, scale, shift, *, relu: bool = False,
          interpret: Optional[bool] = None):
    """Fused 1×1 conv (an MXU matmul over flattened positions) + folded
    BN affine + optional relu: ``(B, H, W, Cin) → (B, H, W, Cout)``.
    Raw entry point — production code routes through
    :func:`route_pw1x1`."""
    b, h, w, cin = x.shape
    cout = w2.shape[-1]
    n = b * h * w
    geom = _pw1x1_geometry(n, cin, cout, x.dtype)
    if geom is None:
        raise ValueError(
            f"pw1x1 site b{b} {h}x{w}x{cin}->{cout} {jnp.dtype(x.dtype)} "
            "exceeds the VMEM block budget")
    r_blk, n_pad = geom
    x2 = x.reshape(n, cin)
    if n_pad > n:
        x2 = jnp.pad(x2, ((0, n_pad - n), (0, 0)))
    grid = n_pad // r_blk
    y2 = pl.pallas_call(
        functools.partial(_pw1x1_kernel, relu=relu),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((r_blk, cin), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((cin, cout), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cout), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cout), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r_blk, cout), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n_pad, cout), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=n * cin * cout * 2,
            bytes_accessed=(n_pad * (cin + cout))
            * jnp.dtype(x.dtype).itemsize,
            transcendentals=0,
        ),
        interpret=INTERPRET if interpret is None else interpret,
    )(x2, w2, scale, shift)
    return y2[:n].reshape(b, h, w, cout)


def _resize_matrix(src: int, dst: int) -> np.ndarray:
    """(dst, src) bilinear interpolation weights reproducing
    ``jax.image.resize(method="bilinear", antialias=False)`` semantics
    (half-pixel centers: src coord = (t + 0.5)·src/dst − 0.5, triangle
    kernel, edge-clamped) — host-computed once per (src, dst) pair so
    the resize becomes two matmuls."""
    scale = src / dst
    out = np.zeros((dst, src), np.float32)
    for t in range(dst):
        s = (t + 0.5) * scale - 0.5
        lo = int(np.floor(s))
        frac = s - lo
        for tap, wgt in ((lo, 1.0 - frac), (lo + 1, frac)):
            out[t, min(max(tap, 0), src - 1)] += wgt
    return out


def _preproc_kernel(x_ref, wh_ref, wwt_ref, o_ref):
    x = x_ref[0]  # (H, W)
    if jnp.issubdtype(x.dtype, jnp.integer):
        # Mosaic has no uint8 -> float32 cast; widening through int32
        # first is exact and lowers (the v5e compile tests pin this)
        x = x.astype(jnp.int32)
    x = x.astype(jnp.float32)
    t = jnp.dot(wh_ref[:], x, preferred_element_type=jnp.float32)
    y = jnp.dot(t, wwt_ref[:], preferred_element_type=jnp.float32)
    o_ref[0] = y.astype(o_ref.dtype)


def preproc_resize(x, target_hw: Tuple[int, int], out_dtype,
                   *, interpret: Optional[bool] = None):
    """Fused cast + bilinear resize, one launch: ``(B, H, W, C)`` any
    dtype (uint8 on the columnar plane) → ``(B, th, tw, C)``
    ``out_dtype``. Channel-planar layout: each grid step resizes one
    (H, W) plane as two interpolation-matrix matmuls (Wh @ X @ WwT).
    Raw entry point — production code routes through
    :func:`route_preproc`."""
    b, h, w, c = x.shape
    th, tw = int(target_hw[0]), int(target_hw[1])
    if not _preproc_geometry(h, w, th, tw):
        raise ValueError(
            f"preproc site {h}x{w}->{th}x{tw} exceeds the VMEM block "
            "budget")
    xp = jnp.transpose(x, (0, 3, 1, 2)).reshape(b * c, h, w)
    wh = jnp.asarray(_resize_matrix(h, th))
    wwt = jnp.asarray(_resize_matrix(w, tw).T)
    y = pl.pallas_call(
        _preproc_kernel,
        grid=(b * c,),
        in_specs=[
            pl.BlockSpec((1, h, w), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((th, h), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((w, tw), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, th, tw), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b * c, th, tw),
                                       jnp.dtype(out_dtype)),
        cost_estimate=pl.CostEstimate(
            flops=b * c * (th * h * w + th * tw * w) * 2,
            bytes_accessed=x.size * jnp.dtype(x.dtype).itemsize
            + b * c * th * tw * jnp.dtype(out_dtype).itemsize,
            transcendentals=0,
        ),
        interpret=INTERPRET if interpret is None else interpret,
    )(xp, wh, wwt)
    return jnp.transpose(y.reshape(b, c, th, tw), (0, 2, 3, 1))


# ---------------------------------------------------------------------------
# XLA twins — the exact op order of the Flax layers the kernels replace
# ---------------------------------------------------------------------------

_DIMS = ("NHWC", "HWIO", "NHWC")


def _bn_reference(y, gamma, beta, mean, var, eps):
    # flax.linen.BatchNorm inference order: (x - mean) * (scale *
    # rsqrt(var + eps)) + bias — NOT the folded affine; fp32 exactness
    # of a candidate is judged against THIS.
    mul = jax.lax.rsqrt(var + jnp.asarray(eps, var.dtype))
    if gamma is not None:
        mul = mul * gamma
    return (y - mean) * mul + beta


def xla_sep2d(x, dw4, pw4, gamma, beta, mean, var, eps,
              relu_in: bool = False):
    """XLA twin of :func:`sep2d` (grouped conv → 1×1 conv → BN)."""
    cin = x.shape[-1]
    if relu_in:
        x = jnp.maximum(x, 0)
    t = jax.lax.conv_general_dilated(
        x, dw4, (1, 1), "SAME", dimension_numbers=_DIMS,
        feature_group_count=cin)
    y = jax.lax.conv_general_dilated(
        t, pw4, (1, 1), "SAME", dimension_numbers=_DIMS)
    return _bn_reference(y, gamma, beta, mean, var, eps)


def xla_pw1x1(x, w4, gamma, beta, mean, var, eps, relu: bool = False):
    """XLA twin of :func:`pw1x1` (1×1 conv → BN → relu?)."""
    y = jax.lax.conv_general_dilated(
        x, w4, (1, 1), "SAME", dimension_numbers=_DIMS)
    y = _bn_reference(y, gamma, beta, mean, var, eps)
    return jnp.maximum(y, 0) if relu else y


def xla_preproc(x, target_hw: Tuple[int, int], out_dtype):
    """XLA twin of :func:`preproc_resize` (cast → jax.image.resize)."""
    th, tw = int(target_hw[0]), int(target_hw[1])
    xf = x.astype(jnp.dtype(out_dtype))
    return jax.image.resize(xf, (x.shape[0], th, tw, x.shape[3]),
                            method="bilinear", antialias=False)


def _fold_bn(gamma, beta, mean, var, eps, cout: int):
    """BN → per-channel affine (float32): scale = γ·rsqrt(var + eps),
    shift = β − mean·scale, shaped (1, Cout) for the kernel epilogue."""
    var32 = var.astype(jnp.float32)
    scale = jax.lax.rsqrt(var32 + jnp.float32(eps))
    if gamma is not None:
        scale = scale * gamma.astype(jnp.float32)
    shift = beta.astype(jnp.float32) - mean.astype(jnp.float32) * scale
    return scale.reshape(1, cout), shift.reshape(1, cout)


# ---------------------------------------------------------------------------
# Routes — the ONLY entry points models use
# ---------------------------------------------------------------------------


def route_sep2d(x, dw_kernel, pw_kernel, bn_scale, bn_bias, bn_mean,
                bn_var, eps, *, family: str):
    """The fused sepconv body for this site, or None (caller keeps its
    XLA path — byte-identical program when nothing is adopted)."""
    b, h, w, cin = x.shape
    cout = pw_kernel.shape[-1]
    site = Site("sep2d", family, (b, h, w, cin, cout), str(x.dtype))
    feasible = _sep2d_geometry(b, h, w, cin, cout, x.dtype) is not None
    if not _decide(site, feasible):
        return None
    dw9 = dw_kernel.reshape(9, cin).astype(x.dtype)
    pw2 = pw_kernel.reshape(cin, cout).astype(x.dtype)
    scale, shift = _fold_bn(bn_scale, bn_bias, bn_mean, bn_var, eps, cout)
    return sep2d(x, dw9, pw2, scale, shift)


def route_pw1x1(x, kernel, bn_scale, bn_bias, bn_mean, bn_var, eps,
                *, relu: bool, family: str):
    """The fused 1×1 ConvBN body for this site, or None."""
    b, h, w, cin = x.shape
    cout = kernel.shape[-1]
    variant = "pw1x1_relu" if relu else "pw1x1"
    site = Site(variant, family, (b, h, w, cin, cout), str(x.dtype))
    feasible = _pw1x1_geometry(b * h * w, cin, cout, x.dtype) is not None
    if not _decide(site, feasible):
        return None
    w2 = kernel.reshape(cin, cout).astype(x.dtype)
    scale, shift = _fold_bn(bn_scale, bn_bias, bn_mean, bn_var, eps, cout)
    return pw1x1(x, w2, scale, shift, relu=relu)


def route_preproc(x, target_hw: Tuple[int, int], out_dtype,
                  *, family: str):
    """The fused cast+resize prologue for this site, or None."""
    b, h, w, c = x.shape
    th, tw = int(target_hw[0]), int(target_hw[1])
    site = Site("preproc", family, (b, h, w, c, th, tw),
                f"{jnp.dtype(x.dtype)}->{jnp.dtype(out_dtype)}")
    if not _decide(site, _preproc_geometry(h, w, th, tw)):
        return None
    return preproc_resize(x, (th, tw), out_dtype)


# ---------------------------------------------------------------------------
# The shootout (accept-if-faster + numeric contract)
# ---------------------------------------------------------------------------


class _Unsupported(RuntimeError):
    pass


def _backend_supported() -> bool:
    return INTERPRET or jax.default_backend() == "tpu"


_AUDITION_EPS = 1e-3  # keras BN default; verdict-neutral (not keyed)


def _build_shootout(site: Site):
    """(pallas_fn, xla_fn, x) at the site's exact shape with synthetic
    O(1)-magnitude operands (so the bf16 tolerance bound is
    meaningful). Parameters close over the functions as constants —
    only the activation is a traced argument."""
    rng = np.random.default_rng(0)
    if site.kernel == "preproc":
        b, h, w, c, th, tw = site.shape
        in_dt, out_dt = site.dtype.split("->")
        x = rng.integers(0, 256, size=(b, h, w, c), dtype=np.uint8) \
            if np.dtype(in_dt) == np.uint8 \
            else rng.standard_normal(size=(b, h, w, c), dtype=np.float32) \
            .astype(in_dt)
        return (lambda a: preproc_resize(a, (th, tw), out_dt),
                lambda a: xla_preproc(a, (th, tw), out_dt),
                jnp.asarray(x))
    b, h, w, cin, cout = site.shape
    dt = jnp.dtype(site.dtype.replace("pw1x1_relu", "")
                   if "->" not in site.dtype else "float32")
    x = jnp.asarray(
        rng.standard_normal(size=(b, h, w, cin), dtype=np.float32), dt)
    gamma = jnp.asarray(
        (np.abs(rng.normal(size=cout)) + 0.5).astype(np.float32))
    beta = jnp.asarray((rng.normal(size=cout) * 0.1).astype(np.float32))
    mean = jnp.asarray((rng.normal(size=cout) * 0.1).astype(np.float32))
    var = jnp.asarray(
        (np.abs(rng.normal(size=cout)) + 1.0).astype(np.float32))
    if site.kernel == "sep2d":
        dw = (rng.normal(size=(3, 3, 1, cin)) * 0.2).astype(np.float32)
        pw = (rng.normal(size=(1, 1, cin, cout))
              * (1.0 / np.sqrt(cin))).astype(np.float32)
        dw4, pw4 = jnp.asarray(dw, dt), jnp.asarray(pw, dt)
        scale, shift = _fold_bn(gamma, beta, mean, var, _AUDITION_EPS,
                                cout)
        dw9 = dw4.reshape(9, cin)
        pw2 = pw4.reshape(cin, cout)
        return (lambda a: sep2d(a, dw9, pw2, scale, shift),
                lambda a: xla_sep2d(a, dw4, pw4, gamma.astype(dt),
                                    beta.astype(dt), mean.astype(dt),
                                    var.astype(dt), _AUDITION_EPS),
                x)
    # pw1x1 / pw1x1_relu
    relu = site.kernel == "pw1x1_relu"
    w4 = jnp.asarray((rng.normal(size=(1, 1, cin, cout))
                      * (1.0 / np.sqrt(cin))).astype(np.float32), dt)
    scale, shift = _fold_bn(gamma, beta, mean, var, _AUDITION_EPS, cout)
    w2 = w4.reshape(cin, cout)
    return (lambda a: pw1x1(a, w2, scale, shift, relu=relu),
            lambda a: xla_pw1x1(a, w4, gamma.astype(dt), beta.astype(dt),
                                mean.astype(dt), var.astype(dt),
                                _AUDITION_EPS, relu=relu),
            x)


def _time_jitted(fn, x, repeats: int = 5, inner: int = 3) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = None
        for _ in range(inner):
            out = fn(x)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def _audition(site: Site) -> Dict[str, Any]:
    """One shootout: build both candidates at the site's shape, check
    the numeric contract, time both, decide. No exception path crashes
    the caller (the XLA path always remains shippable), but only "this
    backend has no Mosaic lowering" (the CPU test suite) is a clean
    rejection. An audition that raises on a backend that DOES lower
    Mosaic (TPU, interpret mode) — the chip's compiler refusing the
    kernel, out of memory — carries ``error`` in its verdict, logs at
    WARNING and counts under ``sparkdl.kernel.audition_error``:
    :func:`ensure_verdict` never persists it, so a repaired kernel is
    auditioned again by the next process."""
    t0 = time.perf_counter()
    verdict: Dict[str, Any] = {"adopted": False, "backend": _backend_tag()}
    try:
        if not _backend_supported():
            raise _Unsupported(
                f"backend {jax.default_backend()!r} has no Mosaic "
                "lowering (set kernels.INTERPRET for interpreter-mode "
                "tests)")
        pallas_fn, xla_fn, x = _build_shootout(site)
        jp, jx = jax.jit(pallas_fn), jax.jit(xla_fn)
        y_x = jax.block_until_ready(jx(x))
        y_p = jax.block_until_ready(jp(x))  # raises if it cannot lower
        a = np.asarray(jnp.asarray(y_p, jnp.float32))
        b = np.asarray(jnp.asarray(y_x, jnp.float32))
        err = float(np.max(np.abs(a - b))) if a.size else 0.0
        verdict["max_abs_err"] = err
        out_dt = np.asarray(y_x).dtype
        if out_dt == np.float32:
            numeric_ok = bool(np.array_equal(a, b))
            contract = "fp32-exact"
        else:
            numeric_ok = err <= BF16_TOLERANCE
            contract = f"max-abs<={BF16_TOLERANCE}"
        xla_s = _time_jitted(jx, x)
        pallas_s = _time_jitted(jp, x)
        verdict["xla_s"] = xla_s
        verdict["pallas_s"] = pallas_s
        if not numeric_ok:
            verdict["reason"] = (f"numeric contract violated "
                                 f"({contract}, err={err:.3g})")
        elif pallas_s > ADOPT_SPEEDUP * xla_s:
            verdict["reason"] = (f"not faster (pallas {pallas_s * 1e6:.0f}"
                                 f"us vs xla {xla_s * 1e6:.0f}us, needs "
                                 f"<= {ADOPT_SPEEDUP:.2f}x)")
        else:
            verdict["adopted"] = True
            verdict["reason"] = (f"{xla_s / max(pallas_s, 1e-12):.2f}x "
                                 "speedup, numerics in contract")
    except _Unsupported as e:
        verdict["reason"] = f"{type(e).__name__}: {e}"
    except Exception as e:  # sparkdl: allow(broad-retry): ANY audition
        # failure (lowering error, OOM) must leave the XLA path shipping
        # — but visibly: the backend claimed Mosaic support, so this is a
        # broken kernel, not a lost shoot-out
        verdict["reason"] = verdict["error"] = f"{type(e).__name__}: {e}"
    dt = time.perf_counter() - t0
    verdict["audition_s"] = dt
    if telemetry.active() is not None:
        telemetry.observe(telemetry.M_KERNEL_AUTOTUNE_S, dt)
        telemetry.count(telemetry.M_KERNEL_AUDITION_ERROR
                        if "error" in verdict
                        else telemetry.M_KERNEL_ADOPTED if verdict["adopted"]
                        else telemetry.M_KERNEL_REJECTED)
    if "error" in verdict:
        logger.warning("kernel audition %s FAILED on a backend with Mosaic "
                       "lowering (XLA path kept, verdict not persisted): %s",
                       _site_key(site), verdict["error"])
    else:
        logger.info("kernel audition %s: %s — %s", _site_key(site),
                    "ADOPTED" if verdict["adopted"] else "rejected",
                    verdict["reason"])
    return verdict


def ensure_verdict(site: Site) -> Dict[str, Any]:
    """The settled verdict for ``site``, running the shootout once if
    this (site, backend) was never auditioned. Single-flight per site:
    a concurrent caller of the same site waits for the owner's verdict
    instead of double-timing the hardware."""
    key = _site_key(site)
    while True:
        found = verdict_for(site)
        if found is not None:
            return found
        with _verdict_lock:
            event = _inflight.get(key)
            if event is None:
                event = threading.Event()
                _inflight[key] = event
                owner = True
            else:
                owner = False
        if not owner:
            event.wait()
            continue  # owner settled (or died trying) — re-read
        try:
            verdict = _audition(site)
            with _verdict_lock:
                _verdicts[key] = verdict
            if "error" not in verdict:
                _persist_verdict(key, verdict)
            return verdict
        finally:
            with _verdict_lock:
                _inflight.pop(key, None)
            event.set()
