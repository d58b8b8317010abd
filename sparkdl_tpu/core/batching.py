"""Static-shape batching utilities.

XLA compiles one program per input shape; variable row counts per partition
would retrace endlessly. Everything device-bound therefore runs at a fixed
``batch_size``: partitions are chunked, the tail chunk is zero-padded and
the pad rows dropped after compute. (The reference had the same constraint
implicitly — TF graphs with fixed input sizes; SURVEY.md §7 "Dynamic
shapes".)
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

import numpy as np

from sparkdl_tpu.core import health, profiling, resilience, telemetry

logger = logging.getLogger(__name__)

# Levers of the staging path (transfer rates and fetch latency are not
# measured on the current machine; the behaviour below is kept until they
# are): transfer uint8 not float32 (4x fewer bytes), resize to the model
# input size on the host BEFORE transfer when that shrinks bytes (native
# batch resizer), and fetch each partition's outputs as ONE
# device-concatenated array instead of one fetch per bucket (one fixed
# fetch latency per partition, not per batch).


def pad_batch(arr: np.ndarray, batch_size: int) -> Tuple[np.ndarray, int]:
    """Zero-pad dim 0 up to ``batch_size``; returns (padded, n_valid)."""
    n = arr.shape[0]
    if n == batch_size:
        return arr, n
    if n > batch_size:
        raise ValueError(f"batch of {n} rows exceeds batch_size {batch_size}")
    pad_widths = [(0, batch_size - n)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad_widths), n


def _round_up(value: int, multiple: int) -> int:
    return int(-(-value // multiple) * multiple)


def bucket_size(n: int, batch_size: int, multiple: int = 1,
                min_bucket: int = 8) -> int:
    """Smallest power-of-two bucket ≥ n (capped, rounded up to ``multiple``
    for mesh data-axis divisibility).

    Tail chunks pad to their bucket instead of the full batch_size — a
    32-row partition behind a batch_size=128 transformer transfers 32-ish
    rows, not 128 (4x padding waste measured on the e2e path). Buckets are
    powers of two so compile count stays O(log batch_size).

    The cap is ``batch_size`` rounded up to ``multiple``: rounding AFTER
    capping at the raw batch_size used to return buckets above the cap a
    non-multiple ``batch_size`` implied (e.g. n=40, batch_size=40,
    multiple=16 must give 48 = roundup(40, 16), never more) — the result
    is always ≤ max(roundup(batch_size), roundup(n)).
    """
    b = min_bucket
    while b < n:
        b <<= 1
    cap = batch_size
    if multiple > 1 and cap % multiple:
        cap = _round_up(cap, multiple)
    b = min(b, cap)
    b = max(b, n)  # n > batch_size: bucket covers n (public-helper use)
    if multiple > 1 and b % multiple:
        b = _round_up(b, multiple)
    return b


def iter_batches(arr: np.ndarray, batch_size: int, multiple: int = 1,
                 planner: Optional["BucketPlanner"] = None
                 ) -> Iterator[Tuple[np.ndarray, int]]:
    """Yield (padded_chunk, n_valid) fixed-shape chunks over dim 0; the
    tail chunk pads to its bucket, not full batch_size — the power-of-two
    ladder by default, or ``planner``'s telemetry-tuned ladder."""
    n = arr.shape[0]
    if n == 0:
        return
    for start in range(0, n, batch_size):
        chunk = arr[start:start + batch_size]
        bucket = (planner.plan(len(chunk)) if planner is not None
                  else bucket_size(len(chunk), batch_size, multiple))
        yield pad_batch(chunk, bucket)


def iter_batches_tree(tree, batch_size: int, multiple: int = 1,
                      planner: Optional["BucketPlanner"] = None):
    """``iter_batches`` over a pytree of dim-0-aligned arrays.

    Multi-input models take a dict of arrays sharing the batch dim
    (the reference ``TFTransformer``'s feed-dict analog); every leaf is
    chunked and padded identically.
    """
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    n = leaves[0].shape[0]
    for leaf in leaves[1:]:
        if leaf.shape[0] != n:
            raise ValueError(
                f"multi-input batch dims disagree: {leaf.shape[0]} vs {n}")
    if n == 0:
        return
    for start in range(0, n, batch_size):
        chunk_leaves = []
        n_valid = min(batch_size, n - start)
        bucket = (planner.plan(n_valid) if planner is not None
                  else bucket_size(n_valid, batch_size, multiple))
        for leaf in leaves:
            padded, _ = pad_batch(leaf[start:start + batch_size], bucket)
            chunk_leaves.append(padded)
        yield treedef.unflatten(chunk_leaves), n_valid


def element_signature(tree) -> Tuple:
    """Per-leaf (element shape, dtype) signature of a dim-0-batched pytree.

    The identity under which rows are interchangeable: the executor's
    coalescer only concatenates requests sharing a signature, and the
    empty-output template memoization keys on it.
    """
    import jax

    return tuple((tuple(leaf.shape[1:]), str(leaf.dtype))
                 for leaf in jax.tree_util.tree_leaves(tree))


def _valid_rows(chunk, n_valid: int):
    """Strip pad rows: the original (unpadded) rows of a padded chunk."""
    import jax

    return jax.tree_util.tree_map(lambda leaf: leaf[:n_valid], chunk)


def tree_nbytes(tree) -> int:
    """Bytes of a pytree's array leaves (host or device)."""
    import jax

    return sum(int(getattr(leaf, "nbytes", 0))
               for leaf in jax.tree_util.tree_leaves(tree))


def launch(fn: Callable, chunk, rows: int):
    """``fn(chunk)`` under ``sparkdl.launch``: the host's side of one
    launch — the argument hand-over (the H2D enqueue of a numpy chunk) and
    the async dispatch. Returns without waiting for the device."""
    with profiling.annotate(telemetry.SPAN_LAUNCH, rows=rows,
                            bytes=tree_nbytes(chunk)):
        return fn(chunk)


def fetch(tree, rows: int):
    """Device outputs → host numpy, in two named steps: wait until the
    device has produced them (``sparkdl.device_sync`` — this launch and
    whatever other threads put in front of it), then copy them over
    (``sparkdl.fetch``, counted in ``sparkdl.executor.fetched_bytes``).
    The wait is what the copy would have blocked on anyway; an execution
    error of the launch raises here, in whichever of the two comes first.
    Where this thread's last launch compiled (``sparkdl.compile``), both
    steps are that first launch's and go to the start-up record too."""
    import jax

    first_launch = profiling.first_launch_wait()
    with profiling.annotate(profiling.DEVICE_SYNC, rows=rows):
        jax.block_until_ready(tree)
    nbytes = tree_nbytes(tree)
    with profiling.annotate(telemetry.SPAN_FETCH, rows=rows, bytes=nbytes):
        host = jax.tree_util.tree_map(np.asarray, tree)
    telemetry.count(telemetry.M_FETCHED_BYTES, nbytes)
    profiling.first_launch_done(first_launch)
    return host


def _dispatch_chunk(fn: Callable, chunk, n_valid: int,
                    multiple: int, policy: resilience.RetryPolicy
                    ) -> List[Tuple[object, int]]:
    """Dispatch one padded chunk with classified retry + OOM re-chunking.

    Returns ``[(device_out, n_valid), ...]`` in row order — one pair
    normally, several when an OOM forced the chunk to re-run as smaller
    sub-chunks. Semantics per failure kind (core.resilience):

    - FATAL: propagate immediately; retrying a shape/dtype error replays it.
    - RETRYABLE: bounded backoff retry via ``policy.execute`` (same chunk,
      same shape — one compiled program).
    - OOM: halve the bucket, re-chunk THIS chunk's valid rows, recurse —
      the padded rows are zeros, so dropping them and re-padding at the
      smaller bucket computes the same per-row values (outputs stay
      bit-identical and order-preserving). An OOM at the minimal bucket
      (≤ the mesh data-axis multiple) propagates to apply_batch's
      whole-call fallback.
    """
    import jax

    rows = jax.tree_util.tree_leaves(chunk)[0].shape[0]

    def attempt():
        resilience.inject("device_oom", rows=rows, valid=n_valid)
        resilience.inject("transfer_stall", rows=rows)
        # dispatched async; no block here
        return [(launch(fn, chunk, rows), n_valid)]

    try:
        return policy.execute(
            attempt, what=f"chunk dispatch (bucket {rows})",
            on_retry=lambda a, e: health.record(
                health.CHUNK_RETRY, bucket=rows, attempt=a,
                error=type(e).__name__))
    except Exception as e:  # noqa: BLE001 - classified below
        if resilience.classify(e) != resilience.OOM:
            raise
        half = rows // 2
        if half < max(1, multiple):
            raise
        health.record(health.OOM_RECHUNK, bucket=rows, half=half)
        logger.warning(
            "device OOM at bucket %d (%s); re-chunking %d valid "
            "row(s) at bucket %d", rows, e, n_valid, half)
        out: List[Tuple[object, int]] = []
        for sub, sub_valid in iter_batches_tree(
                _valid_rows(chunk, n_valid), half, multiple):
            out.extend(_dispatch_chunk(fn, sub, sub_valid,
                                       multiple, policy))
        return out


# Memoized empty-output templates: (id(fn), element shapes/dtypes) →
# (weakref-to-fn, output element shapes/dtypes + treedef). The fn is held
# WEAKLY with a drop-on-collect callback, so memoization never pins a
# discarded model's jitted closure (and the weights it captures); the
# stored ref also guards against an id() recycled onto a different fn.
# Non-weakref-able callables fall back to a strong ref (rare; bounded by
# the caller's own lifetime management).
_EMPTY_TEMPLATES: Dict[Tuple, Tuple[Callable[[], Any], Any]] = {}
_EMPTY_LOCK = threading.Lock()


def _empty_result(fn: Callable, tree, batch_size: int):
    """Zero-row output matching ``fn``'s output element shapes.

    The shape inference (``jax.eval_shape`` — a full trace) runs once per
    (fn, input element shape/dtype) and is memoized: the output element
    shape does not depend on the batch size, so every later empty call
    rebuilds the zero-row arrays from the cached template. The trace uses
    ``fn.__sparkdl_trace_target__`` when present (``ModelFunction.jitted``'s
    compile-span wrapper exposes it; a dedicated attribute so a caller's
    own functools-wrapped fn is never unwrapped by accident): tracing the
    wrapper itself would record a phantom compile span and hide the real
    first-launch one.
    """
    import weakref

    import jax

    key = (id(fn), element_signature(tree))
    with _EMPTY_LOCK:
        hit = _EMPTY_TEMPLATES.get(key)
    if hit is None or hit[0]() is not fn:
        dummy_in = jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(
                (batch_size,) + leaf.shape[1:], leaf.dtype), tree)
        dummy = jax.eval_shape(
            getattr(fn, "__sparkdl_trace_target__", fn), dummy_in)
        out_leaves, treedef_out = jax.tree_util.tree_flatten(dummy)
        template = ([(tuple(d.shape[1:]), np.dtype(d.dtype))
                     for d in out_leaves], treedef_out)

        def _drop(_ref, _key=key):
            with _EMPTY_LOCK:
                _EMPTY_TEMPLATES.pop(_key, None)

        try:
            ref: Callable[[], Any] = weakref.ref(fn, _drop)
        except TypeError:  # non-weakref-able callable: strong fallback
            ref = (lambda _fn=fn: _fn)
        with _EMPTY_LOCK:
            _EMPTY_TEMPLATES[key] = (ref, template)
    else:
        template = hit[1]
    elements, treedef_out = template
    return treedef_out.unflatten(
        [np.zeros((0,) + shape, dtype=dtype) for shape, dtype in elements])


def _record_chunk_metrics(chunk, n_valid: int) -> None:
    """Feed the active telemetry scope's bucket-occupancy / padding-waste
    instruments (docs/OBSERVABILITY.md metric catalog). One global read
    when no scope is active."""
    tel = telemetry.active()
    if tel is None:
        return
    import jax

    bucket = jax.tree_util.tree_leaves(chunk)[0].shape[0]
    valid = tel.metrics.counter(telemetry.M_BATCH_ROWS)
    pad = tel.metrics.counter(telemetry.M_BATCH_PAD_ROWS)
    valid.inc(n_valid)
    pad.inc(bucket - n_valid)
    tel.metrics.histogram(telemetry.M_BATCH_BUCKET_ROWS,
                          telemetry.POW2_BOUNDS).observe(bucket)
    total = valid.value + pad.value
    if total:
        tel.metrics.gauge(telemetry.M_PADDING_WASTE).set(
            pad.value / total)


# ---------------------------------------------------------------------------
# Telemetry-tuned bucket ladder (docs/PERF.md "Launch shaping & precision")
# ---------------------------------------------------------------------------

#: Retune cadence: the ladder is re-solved every N observed launches.
PLANNER_UPDATE_EVERY = 64
#: Hysteresis: a candidate ladder is adopted only when it cuts the
#: predicted pad rows by at least this fraction vs the current ladder —
#: marginal wins never pay a recompile.
PLANNER_HYSTERESIS = 0.10
#: Hard bound on ladder adoptions per planner: with the rung count capped
#: at the power-of-two ladder's length, total compile count stays
#: O(log batch_size) for the process lifetime.
PLANNER_MAX_UPDATES = 8
#: Observed-size histogram bound (distinct sizes kept exactly; partition
#: sizes are highly repetitive in practice).
_PLANNER_MAX_SIZES = 128

_LADDER_STORE_BASENAME = "sparkdl_bucket_ladders.json"


def ladder_store_path() -> Optional[str]:
    """Learned-ladder persistence file, beside the persistent compilation
    cache when ``$JAX_COMPILATION_CACHE_DIR`` names it: a warm process
    reloads the tuned ladder together with the compiled programs it
    selected, so the retune (and its compiles) are paid once per cluster,
    not per process. None otherwise (no persistence)."""
    import os

    from sparkdl_tpu import _sidecar_store_dir

    store_dir = _sidecar_store_dir()
    if store_dir is None:
        return None
    return os.path.join(store_dir, _LADDER_STORE_BASENAME)


def _pow2_ladder(batch_size: int, multiple: int, min_bucket: int
                 ) -> Tuple[int, ...]:
    """The blind ladder: every bucket ``bucket_size`` can return for
    n ≤ batch_size. Seeding the planner with it makes a cold planner
    byte-identical to the unplanned path."""
    rungs = set()
    b = min_bucket
    n = 1
    while n <= batch_size:
        rungs.add(bucket_size(n, batch_size, multiple, min_bucket))
        if n == b:
            b <<= 1
        n = min(b, batch_size) if n < batch_size else batch_size + 1
    return tuple(sorted(rungs))


class BucketPlanner:
    """Per-compiled-fn telemetry-tuned bucket ladder.

    Feeds on the same launch-size stream that drives the padding-waste
    gauge and the ``sparkdl.executor.coalesce_rows`` /
    ``coalesce_requests`` histograms (``plan``/``observe`` are called at
    exactly the call sites that feed those instruments), and periodically
    re-solves the ladder to minimize predicted pad rows over the observed
    size distribution. Bounded: at most as many rungs as the power-of-two
    ladder, adoption gated on a ≥ ``PLANNER_HYSTERESIS`` predicted win
    (and at most ``PLANNER_MAX_UPDATES`` adoptions), so compile count
    stays O(log batch_size). When a telemetry scope is active, each
    adoption bumps ``sparkdl.batching.bucket_ladder_update`` and sets the
    ``sparkdl.batching.planner_waste`` gauge to the predicted pad
    fraction under the new ladder. Thread-safe.
    """

    def __init__(self, batch_size: int, multiple: int = 1,
                 min_bucket: int = 8, name: str = "model",
                 update_every: int = PLANNER_UPDATE_EVERY,
                 hysteresis: float = PLANNER_HYSTERESIS,
                 ladder: Optional[Tuple[int, ...]] = None) -> None:
        self.batch_size = int(batch_size)
        self.multiple = max(1, int(multiple))
        self.min_bucket = int(min_bucket)
        self.name = name
        self.update_every = max(1, int(update_every))
        self.hysteresis = float(hysteresis)
        self._lock = threading.Lock()
        self._counts: Dict[int, int] = {}
        self._since_update = 0
        self._updates = 0
        self._cap = bucket_size(self.batch_size, self.batch_size,
                                self.multiple, self.min_bucket)
        base = _pow2_ladder(self.batch_size, self.multiple, self.min_bucket)
        self._ladder: Tuple[int, ...] = (
            tuple(sorted(set(ladder))) if ladder else base)
        # the top rung must cover every admissible n (≤ batch_size)
        if not self._ladder or self._ladder[-1] < self._cap:
            self._ladder = tuple(sorted(set(self._ladder) | {self._cap}))

    # -- lookup ---------------------------------------------------------------

    def ladder(self) -> Tuple[int, ...]:
        with self._lock:
            return self._ladder

    def bucket_for(self, n: int, cap: Optional[int] = None) -> int:
        """Smallest ladder rung ≥ n. ``cap`` below this planner's
        batch_size (a tighter ``coalesce_max_rows``) falls back to the
        blind ladder at that cap — a foreign cap must not graft new
        shapes onto the tuned ladder."""
        if cap is not None and cap < self.batch_size:
            return bucket_size(n, cap, self.multiple, self.min_bucket)
        with self._lock:
            for rung in self._ladder:
                if rung >= n:
                    return rung
        # n above the ladder (public-helper use): cover it
        return bucket_size(n, self.batch_size, self.multiple,
                           self.min_bucket)

    def plan(self, n: int) -> int:
        """``observe`` + ``bucket_for`` — the one-call form the batching
        iterators use per chunk."""
        self.observe(n)
        return self.bucket_for(n)

    # -- learning -------------------------------------------------------------

    def observe(self, n: int) -> None:
        """Record one requested launch of ``n`` valid rows; retune every
        ``update_every`` observations."""
        if n <= 0 or n > self.batch_size:
            return
        retune = False
        with self._lock:
            if len(self._counts) < _PLANNER_MAX_SIZES or n in self._counts:
                self._counts[n] = self._counts.get(n, 0) + 1
            self._since_update += 1
            if (self._since_update >= self.update_every
                    and self._updates < PLANNER_MAX_UPDATES):
                self._since_update = 0
                retune = True
        if retune:
            self._retune()

    def _padded_rows(self, ladder: Tuple[int, ...],
                     counts: Dict[int, int]) -> float:
        total = 0.0
        for n, c in counts.items():
            rung = next((r for r in ladder if r >= n), self._cap)
            total += c * (rung - n)
        return total

    def _retune(self) -> None:
        """Re-solve the ladder over the observed size histogram (exact DP
        over candidate rungs — distinct observed sizes are few), gated on
        hysteresis. Reads the live padding-waste gauge as a cheap trigger:
        when the measured waste is already negligible there is nothing to
        win and no recompile is worth paying."""
        tel = telemetry.active()
        if tel is not None:
            waste = tel.metrics.gauge(telemetry.M_PADDING_WASTE).value
            if waste is not None and waste < 0.02:
                return
        with self._lock:
            counts = dict(self._counts)
            current = self._ladder
            max_rungs = len(_pow2_ladder(self.batch_size, self.multiple,
                                         self.min_bucket))
        if not counts:
            return
        candidate = self._solve(counts, max_rungs)
        cost_now = self._padded_rows(current, counts)
        cost_new = self._padded_rows(candidate, counts)
        if candidate == current or cost_new > (1.0 - self.hysteresis) * cost_now:
            return
        with self._lock:
            self._ladder = candidate
            self._updates += 1
        valid = float(sum(n * c for n, c in counts.items()))
        waste_after = (cost_new / (cost_new + valid)
                       if cost_new + valid else 0.0)
        logger.info("%s: bucket ladder retuned to %s (predicted pad "
                    "fraction %.3f)", self.name, candidate, waste_after)
        if telemetry.active() is not None:
            telemetry.count(telemetry.M_BUCKET_LADDER_UPDATE)
            telemetry.gauge_set(telemetry.M_PLANNER_WASTE, waste_after)
        _persist_ladder(self)

    def _solve(self, counts: Dict[int, int], max_rungs: int
               ) -> Tuple[int, ...]:
        """Pick ≤ max_rungs rungs minimizing total pad rows over the
        observed sizes. Candidates are the observed sizes rounded up to
        the mesh multiple, plus the cap (which is always a rung so any
        n ≤ batch_size stays coverable). Exact DP: for S candidates,
        O(S² · max_rungs) — S is small by construction."""
        cands = sorted({min(_round_up(n, self.multiple), self._cap)
                        for n in counts} | {self._cap})
        sizes = sorted(counts)
        # weight[j] = rows observed at size ≤ cands[j] but > cands[j-1]
        # cost(i, j): pad rows of sizes in (cands[i], cands[j]] padded to
        # cands[j] (sizes ≤ cands[i] are covered by a lower rung).
        INF = float("inf")

        def seg_cost(lo: int, hi: int) -> float:
            # pad-to-hi cost of every observed size in (lo, hi]
            return sum(c * (hi - n) for n, c in counts.items()
                       if lo < n <= hi)

        S = len(cands)
        # dp[k][j]: min cost covering all sizes ≤ cands[j] with k rungs,
        # the highest being cands[j]
        dp = [[INF] * S for _ in range(max_rungs + 1)]
        choice: Dict[Tuple[int, int], int] = {}
        for j in range(S):
            dp[1][j] = seg_cost(0, cands[j])
        for k in range(2, max_rungs + 1):
            for j in range(S):
                best, arg = dp[k - 1][j], None  # k-1 rungs already enough
                for i in range(j):
                    c = dp[k - 1][i] + seg_cost(cands[i], cands[j])
                    if c < best:
                        best, arg = c, i
                dp[k][j] = best
                if arg is not None:
                    choice[(k, j)] = arg
        # top rung must be the cap rung (last candidate)
        j = S - 1
        k = max_rungs
        rungs = [cands[j]]
        while k > 1:
            arg = choice.get((k, j))
            if arg is None:
                k -= 1
                continue
            j = arg
            rungs.append(cands[j])
            k -= 1
        return tuple(sorted(set(rungs)))

    # -- persistence ----------------------------------------------------------

    def _store_key(self) -> str:
        return f"{self.name}|{self.batch_size}|{self.multiple}"


def _persist_ladder(planner: BucketPlanner) -> None:
    """Merge this planner's ladder into the store file (atomic replace;
    concurrent writers race whole-file, last wins — the ladder is a cache,
    not a source of truth)."""
    import json
    import os

    path = ladder_store_path()
    if path is None:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        doc = {"version": 1, "ladders": {}}
        try:
            with open(path) as f:
                loaded = json.load(f)
            if isinstance(loaded, dict) and loaded.get("version") == 1:
                doc = loaded
        except (OSError, ValueError):
            pass
        doc.setdefault("ladders", {})[planner._store_key()] = \
            list(planner.ladder())
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
    except OSError as e:  # persistence is best-effort
        logger.warning("could not persist bucket ladder to %s: %s", path, e)


def _load_ladder(name: str, batch_size: int, multiple: int
                 ) -> Optional[Tuple[int, ...]]:
    import json

    path = ladder_store_path()
    if path is None:
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
        rungs = doc.get("ladders", {}).get(f"{name}|{batch_size}|{multiple}")
        if rungs and all(isinstance(r, int) and r > 0 for r in rungs):
            return tuple(sorted(set(rungs)))
    except (OSError, ValueError, AttributeError):
        pass
    return None


# Process-wide planner registry: the executor's coalesced launches and the
# chunked apply_batch path share one planner per (model, batch_size,
# multiple), so both feed (and benefit from) the same learned ladder.
_PLANNERS: Dict[Tuple, BucketPlanner] = {}
_PLANNER_LOCK = threading.Lock()


def planner_for(name: str, batch_size: int, multiple: int = 1,
                min_bucket: int = 8) -> BucketPlanner:
    """The shared planner for one (model name, batch_size, multiple)
    ladder; created seeded with the persisted ladder when one exists."""
    key = (name, int(batch_size), int(multiple))
    with _PLANNER_LOCK:
        planner = _PLANNERS.get(key)
    if planner is not None:
        return planner
    # persisted-ladder file I/O stays OUTSIDE the lock; two racers build
    # equivalent planners and setdefault keeps exactly one
    planner = BucketPlanner(batch_size, multiple, min_bucket=min_bucket,
                            name=name,
                            ladder=_load_ladder(name, batch_size, multiple))
    with _PLANNER_LOCK:
        return _PLANNERS.setdefault(key, planner)


def default_planner(name: str, batch_size: int, multiple: int = 1
                    ) -> Optional[BucketPlanner]:
    """``planner_for`` gated on ``EngineConfig.bucket_ladder``: None under
    ``"pow2"`` (the escape hatch restores the blind ladder everywhere).
    Core stays importable without the engine — no engine, no knob, tuned
    by default."""
    try:
        from sparkdl_tpu.engine.dataframe import EngineConfig
        mode = EngineConfig.bucket_ladder
    except ImportError:  # pragma: no cover - engine-less deployments
        mode = "tuned"
    if mode != "tuned":
        return None
    return planner_for(name, batch_size, multiple)


def reset_planners() -> None:
    """Drop every learned ladder (test/bench isolation)."""
    with _PLANNER_LOCK:
        _PLANNERS.clear()


def run_batched(fn: Callable, tree, batch_size: int,
                multiple: int = 1,
                retry_policy: Optional[resilience.RetryPolicy] = None,
                prefetch: int = 2,
                planner: Optional[BucketPlanner] = None):
    """Apply a fixed-batch device fn over all rows, concatenating outputs.

    ``tree``: one array or a pytree of dim-0-aligned arrays (multi-input
    models). ``fn`` must accept the padded chunk and return a device array
    (or pytree of them) whose dim 0 aligns with the input rows (jit
    specializes per bucket shape). Host chunk staging (the pad copies of
    ``iter_batches_tree``) runs ``prefetch`` chunks ahead on a background
    staging thread (``core.pipeline.DevicePrefetcher``; 0 = inline), and
    JAX's async dispatch overlaps the H2D transfer + device compute of
    chunk k with the staging of chunk k+1: all chunks are dispatched
    before blocking on any result, and the per-bucket outputs are
    concatenated ON DEVICE so the host pays ONE device→host fetch per
    leaf per call instead of one fetch per bucket. Pad rows
    of a single-bucket call are sliced off ON DEVICE before that fetch —
    a small tail-bucket partition transfers its valid rows only, not up
    to 2× of them. ``multiple``: bucket-size
    divisibility constraint (mesh data axis).

    Per-chunk failures are classified (core.resilience): transient errors
    retry with backoff, device OOM re-chunks at a halved bucket (results
    stay bit-identical and order-preserving), fatal errors propagate.
    Staged chunks stay host-resident numpy, so the OOM re-chunk path
    re-pads on the host exactly as before. ``retry_policy=None`` uses
    ``resilience.DEFAULT_INFERENCE_POLICY``.
    """
    import jax

    from sparkdl_tpu.core import pipeline

    policy = (retry_policy if retry_policy is not None
              else resilience.DEFAULT_INFERENCE_POLICY)
    outs = []
    valids = []
    # single-chunk inputs (the dominant engine featurize case: one
    # partition chunk <= batch_size rows) have no k+1 to stage ahead —
    # skip the staging thread entirely, it could only add overhead
    rows = jax.tree_util.tree_leaves(tree)[0].shape[0]
    if rows <= batch_size:
        prefetch = 0
    with pipeline.DevicePrefetcher(
            iter_batches_tree(tree, batch_size, multiple, planner=planner),
            depth=prefetch, name="run_batched") as staged:
        for chunk, n_valid in staged:
            _record_chunk_metrics(chunk, n_valid)
            for out, v in _dispatch_chunk(fn, chunk, n_valid, multiple,
                                          policy):
                outs.append(out)
                valids.append(v)
    if not outs:
        # Preserve the output *element* shape for empty inputs (memoized
        # per (fn, element shape/dtype) — empty partitions in a
        # quarantined stream must not pay repeated tracing).
        return _empty_result(fn, tree, batch_size)

    flat_outs = [jax.tree_util.tree_flatten(o) for o in outs]
    treedef_out = flat_outs[0][1]
    result_leaves = []
    for j in range(len(flat_outs[0][0])):
        leaf_per_batch = [f[0][j] for f in flat_outs]
        if len(leaf_per_batch) == 1:
            # slice pad rows off ON DEVICE before the fetch: a tail-bucket
            # partition transfers only its valid rows instead of the full
            # padded bucket (ISSUE 3)
            leaf = leaf_per_batch[0]
            if valids[0] < leaf.shape[0]:
                leaf = leaf[:valids[0]]
            result_leaves.append(fetch(leaf, valids[0]))
            continue
        import jax.numpy as jnp

        fetched = fetch(jnp.concatenate(leaf_per_batch, axis=0),
                        sum(valids))
        host = []
        off = 0
        for o, v in zip(leaf_per_batch, valids):
            host.append(fetched[off:off + v])
            off += o.shape[0]
        result_leaves.append(np.concatenate(host, axis=0))
    return treedef_out.unflatten(result_leaves)
