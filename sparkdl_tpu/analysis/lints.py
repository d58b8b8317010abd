"""The six one-off AST lints, migrated onto the shared framework.

These grew one per PR in ``tests/test_taxonomy_lint.py`` (ISSUEs 2–7),
each with its own tree walk and its own suppression spelling. Here they
are registered rules — one engine, one suppression syntax
(``# sparkdl: allow(<rule>): <why>``), one catalog (docs/ANALYSIS.md) —
and the test module shrinks to thin wrappers that invoke the analyzer.

- ``broad-retry`` — no blind broad-except retry loops bypassing
  ``core.resilience.classify`` (ISSUE 2).
- ``blocking-fetch-in-fit`` — no blocking device fetch inside
  ``Trainer.fit``'s step loop (ISSUE 3).
- ``span-names`` — every ``annotate()``/``span()`` name must be in
  ``core.telemetry.CANONICAL_SPAN_NAMES`` (ISSUE 4).
- ``executor-choke-point`` — the featurize route (ml/udf/engine/image)
  enters the device only via ``executor.execute`` (ISSUE 5).
- ``health-constants`` — every ``health.record(...)`` passes a
  ``health.<CONSTANT>`` declared in ``core/health.py`` (ISSUE 6).
- ``slo-metrics`` — every ``SLORule(metric=…)`` statically resolves to
  a declared metric (ISSUE 7).

Constant resolution goes through the LIVE ``core`` modules (telemetry /
profiling / health import nothing heavy), exactly as the original lints
did — a catalog addition is picked up without touching the analyzer.
"""

from __future__ import annotations

import ast
import pathlib
from typing import List, Optional, Tuple

from sparkdl_tpu.analysis.framework import (Finding, Rule, SourceFile,
                                            register)
from sparkdl_tpu.core import health as _health
from sparkdl_tpu.core import profiling as _profiling
from sparkdl_tpu.core import telemetry as _telemetry

# ---------------------------------------------------------------------------
# broad-retry (ISSUE 2)
# ---------------------------------------------------------------------------

_BROAD = {"Exception", "BaseException"}


def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    if isinstance(t, ast.Name):
        return t.id in _BROAD
    if isinstance(t, ast.Tuple):
        return any(isinstance(e, ast.Name) and e.id in _BROAD
                   for e in t.elts)
    return False


def _consults_taxonomy_or_raises(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
        if isinstance(node, ast.Name) and node.id in ("classify",
                                                      "resilience"):
            return True
        if isinstance(node, ast.Attribute) and node.attr == "classify":
            return True
    return False


@register
class BroadRetryRule(Rule):
    id = "broad-retry"
    title = "broad except inside a loop without classify/re-raise"
    rationale = (
        "Inside a for/while loop, an `except:`/`except Exception` "
        "handler that neither re-raises nor consults "
        "core.resilience.classify is the blind-retry shape PR 1/2 "
        "removed — FATAL user errors would be silently replayed. "
        "Deliberate non-retry swallows carry a suppression "
        "justification instead.")

    def check(self, src: SourceFile) -> List[Finding]:
        findings: List[Finding] = []
        loop_depth = 0

        def visit(node: ast.AST) -> None:
            nonlocal loop_depth
            is_loop = isinstance(node, (ast.For, ast.While,
                                        ast.AsyncFor))
            if is_loop:
                loop_depth += 1
            if isinstance(node, (ast.Try, getattr(ast, "TryStar",
                                                  ast.Try))):
                for handler in node.handlers:
                    if (loop_depth > 0 and _is_broad(handler)
                            and not _consults_taxonomy_or_raises(
                                handler)):
                        findings.append(self.finding(
                            src, handler.lineno,
                            "broad except inside a loop without "
                            "re-raise or core.resilience.classify — "
                            "blind retry would replay FATAL errors"))
            for child in ast.iter_child_nodes(node):
                visit(child)
            if is_loop:
                loop_depth -= 1

        visit(src.tree)
        return findings


# ---------------------------------------------------------------------------
# blocking-fetch-in-fit (ISSUE 3)
# ---------------------------------------------------------------------------

_FETCH_NAMES = {"int", "float"}
_FETCH_ATTRS = {"asarray", "device_get", "block_until_ready"}


def blocking_fetches_in_fit(tree: ast.AST) -> List[int]:
    """Lines of blocking-fetch calls inside ``Trainer.fit``'s own loops
    (empty when the tree has no ``Trainer.fit``). Nested function
    DEFINITIONS are exempt — only their call sites block."""
    fit = None
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "Trainer":
            for item in node.body:
                if (isinstance(item, ast.FunctionDef)
                        and item.name == "fit"):
                    fit = item
    if fit is None:
        return []

    loops: List[ast.AST] = []

    def find_loops(node: ast.AST) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue  # helper closures run at sync points, not here
            if isinstance(child, (ast.For, ast.While, ast.AsyncFor)):
                loops.append(child)
            find_loops(child)

    find_loops(fit)

    def walk_pruned(node: ast.AST):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            yield child
            yield from walk_pruned(child)

    violations = []
    for loop in loops:
        for node in walk_pruned(loop):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Name) and f.id in _FETCH_NAMES:
                violations.append(node.lineno)
            elif isinstance(f, ast.Attribute) and f.attr in _FETCH_ATTRS:
                violations.append(node.lineno)
    return sorted(set(violations))


@register
class BlockingFetchInFitRule(Rule):
    id = "blocking-fetch-in-fit"
    title = "blocking device fetch inside Trainer.fit's step loop"
    rationale = (
        "int()/float() on a device scalar, np.asarray, jax.device_get "
        "or block_until_ready inside the fit step loop re-serializes "
        "host staging with device compute — the exact regression the "
        "DevicePrefetcher removed. Fetches belong in the designated "
        "sync helpers, called only at sync points.")

    def check(self, src: SourceFile) -> List[Finding]:
        return [self.finding(
            src, line,
            "blocking device fetch inside Trainer.fit's step loop — "
            "move it into the sync helpers (sync/save_checkpoint) "
            "called only at sync points")
            for line in blocking_fetches_in_fit(src.tree)]


# ---------------------------------------------------------------------------
# span-names (ISSUE 4)
# ---------------------------------------------------------------------------

#: ``remote_span``/``record_remote`` carry span names ACROSS a process
#: boundary (decode-pool / cluster messages): a non-canonical name there
#: is unmergeable on the adopting side, so the lint covers them too —
#: the static half of the runtime rejection in ``Tracer.record_remote``
#: / ``adopt_remote_spans``.
_SPAN_CALL_NAMES = {"annotate", "span", "remote_span", "record_remote"}


def _resolve_span_name(arg: ast.expr) -> Optional[str]:
    """String value of a span-name argument, or None when dynamic."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    attr = None
    if isinstance(arg, ast.Attribute):   # profiling.STAGE_BATCH
        attr = arg.attr
    elif isinstance(arg, ast.Name):      # SPAN_RUN inside telemetry.py
        attr = arg.id
    if attr is not None:
        for mod in (_profiling, _telemetry):
            value = getattr(mod, attr, None)
            if isinstance(value, str):
                return value
    return None


def span_names_in(tree: ast.AST) -> List[Tuple[str, int]]:
    """(name, lineno) for every statically-resolvable
    ``annotate()``/``span()`` call."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        f = node.func
        fname = (f.id if isinstance(f, ast.Name)
                 else f.attr if isinstance(f, ast.Attribute) else None)
        if fname not in _SPAN_CALL_NAMES:
            continue
        name = _resolve_span_name(node.args[0])
        if name is not None:
            out.append((name, node.lineno))
    return out


@register
class SpanNamesRule(Rule):
    id = "span-names"
    title = "annotate()/span()/remote_span() names must be canonical"
    rationale = (
        "A typo'd phase name silently forks a timer and a trace track "
        "instead of failing, and a non-canonical name shipped across a "
        "process boundary (remote_span/record_remote) is REJECTED by "
        "the adopting tracer — the span vanishes from the merged "
        "timeline. Every literal or module-constant name must be "
        "declared in core.telemetry.CANONICAL_SPAN_NAMES "
        "(docs/OBSERVABILITY.md is the human catalog); dynamic names "
        "are not checkable and are skipped.")

    def check(self, src: SourceFile) -> List[Finding]:
        catalog = _telemetry.CANONICAL_SPAN_NAMES
        return [self.finding(
            src, line,
            f"span/phase name {name!r} is not declared in "
            "core.telemetry.CANONICAL_SPAN_NAMES — add it to the "
            "catalog (and docs/OBSERVABILITY.md) or fix the typo")
            for name, line in span_names_in(src.tree)
            if name not in catalog]


# ---------------------------------------------------------------------------
# executor-choke-point (ISSUE 5)
# ---------------------------------------------------------------------------

_DEVICE_ENTRY_ATTRS = {"apply_batch", "jitted", "with_dtype"}
#: The featurize/serving route that MUST go through the executor. The
#: choke point itself (core/executor.py) and the model layer it wraps
#: (core/model_function.py) live outside these scopes by design; the
#: training path (train/) owns its own step programs and is exempt.
#: "serving" covers the online plane (sparkdl_tpu/serving/): row-level
#: requests enter the device ONLY via executor.execute, same as batch.
#: "cluster" covers the multi-process inference plane
#: (sparkdl_tpu/cluster/): a worker's op chain reaches the device via
#: its per-process executor — router/worker code never launches
#: directly.
CHOKE_SCOPES = ("ml", "udf", "engine", "image", "serving", "cluster")


def direct_device_entry_calls(tree: ast.AST) -> List[int]:
    """Lines of direct ``.apply_batch(...)`` / ``.jitted(...)`` /
    ``.with_dtype(...)`` calls. ``jitted`` is flagged with or without
    ``donate_batch=`` — both the donation decision and the launch route
    belong to the executor choke point."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _DEVICE_ENTRY_ATTRS:
            out.append(node.lineno)
    return sorted(out)


@register
class ExecutorChokePointRule(Rule):
    id = "executor-choke-point"
    title = "featurize route must enter the device via executor.execute"
    rationale = (
        "A transformer/UDF/engine op calling apply_batch or jitted "
        "directly silently regresses the featurize route to "
        "per-partition launches (docs/PERF.md 'Cross-partition "
        "coalescing'), invisible until the next bench round; a "
        "per-call-site with_dtype or jitted(donate_batch=...) forks the "
        "precision/donation decision away from "
        "EngineConfig.inference_precision / inference_donate_buffers "
        "(docs/PERF.md 'Launch shaping & precision'). Only the executor "
        "choke point and the model layer it wraps may touch those "
        "methods.")

    def check(self, src: SourceFile) -> List[Finding]:
        parts = set(pathlib.PurePath(src.rel).parts)
        if not parts & set(CHOKE_SCOPES):
            return []
        return [self.finding(
            src, line,
            "direct apply_batch/jitted/with_dtype call on the engine "
            "featurize route — device entry, precision, and donation "
            "must go through core.executor.execute and EngineConfig "
            "(the coalescing choke point)")
            for line in direct_device_entry_calls(src.tree)]


# ---------------------------------------------------------------------------
# health-constants (ISSUE 6)
# ---------------------------------------------------------------------------

#: Event-name constants declared in core/health.py: UPPERCASE module
#: attributes holding strings.
HEALTH_EVENT_CONSTANTS = frozenset(
    name for name in vars(_health)
    if name.isupper() and isinstance(getattr(_health, name), str))


def bad_health_record_calls(tree: ast.AST) -> List[Tuple[int, str]]:
    """(lineno, reason) for every ``health.record(...)`` call whose
    event argument is not a declared ``health.<CONSTANT>``."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        # the framework-wide convention: `health.record(...)` on the
        # imported module object (never `from ... import record`)
        if not (isinstance(f, ast.Attribute) and f.attr == "record"
                and isinstance(f.value, ast.Name)
                and f.value.id == "health"):
            continue
        if not node.args:
            out.append((node.lineno, "no event argument"))
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            out.append((node.lineno, f"bare string {arg.value!r}"))
            continue
        if not (isinstance(arg, ast.Attribute)
                and isinstance(arg.value, ast.Name)
                and arg.value.id == "health"):
            out.append((node.lineno,
                        "event name is not a health.<CONSTANT> "
                        "reference"))
            continue
        if arg.attr not in HEALTH_EVENT_CONSTANTS:
            out.append((node.lineno,
                        f"health.{arg.attr} is not declared in "
                        "core/health.py"))
    return out


@register
class HealthConstantsRule(Rule):
    id = "health-constants"
    title = "health.record() must pass a declared health.<CONSTANT>"
    rationale = (
        "A bare-string or typo'd event name silently forks a counter "
        "outside the docs catalog, the chaos accounting and the "
        "sparkdl.health.* telemetry mirrors. Declare the event in "
        "core/health.py and reference the constant.")

    def check(self, src: SourceFile) -> List[Finding]:
        return [self.finding(
            src, line,
            f"health.record() event argument: {reason} — declare the "
            "event in core/health.py and reference it as "
            "health.<CONSTANT>")
            for line, reason in bad_health_record_calls(src.tree)]


# ---------------------------------------------------------------------------
# slo-metrics (ISSUE 7)
# ---------------------------------------------------------------------------

#: Declared health-event VALUES (the strings the mirrors are named
#: after).
_HEALTH_EVENT_VALUES = frozenset(
    getattr(_health, name) for name in HEALTH_EVENT_CONSTANTS)

_SLO_CONST_MODULES = ("telemetry", "health", "profiling", "slo")
_UNRESOLVED = object()


def _resolve_string_expr(node: ast.expr):
    """Static string value: literals, telemetry./health./profiling.
    module constants (bare names resolve too, for constants referenced
    inside their own module), and ``+`` concatenations of those.
    ``_UNRESOLVED`` for a module-constant reference that does not exist
    (a typo'd constant); None when genuinely dynamic."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    attr = None
    flag_missing = False
    if isinstance(node, ast.Attribute):
        attr = node.attr
        flag_missing = (isinstance(node.value, ast.Name)
                        and node.value.id in _SLO_CONST_MODULES)
    elif isinstance(node, ast.Name):
        attr = node.id
    if attr is not None:
        for mod in (_telemetry, _health, _profiling):
            value = getattr(mod, attr, None)
            if isinstance(value, str):
                return value
        return _UNRESOLVED if flag_missing else None
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        left = _resolve_string_expr(node.left)
        right = _resolve_string_expr(node.right)
        if left is _UNRESOLVED or right is _UNRESOLVED:
            return _UNRESOLVED
        if left is not None and right is not None:
            return left + right
    return None


def bad_slo_rule_metrics(tree: ast.AST) -> List[Tuple[int, str]]:
    """(lineno, reason) for every ``SLORule(...)`` whose metric does
    not statically resolve to a declared metric name."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        fname = (f.id if isinstance(f, ast.Name)
                 else f.attr if isinstance(f, ast.Attribute) else None)
        if fname != "SLORule":
            continue
        metric_arg = None
        for kw in node.keywords:
            if kw.arg == "metric":
                metric_arg = kw.value
        if metric_arg is None and len(node.args) >= 2:
            metric_arg = node.args[1]
        if metric_arg is None:
            out.append((node.lineno, "no metric argument"))
            continue
        metric = _resolve_string_expr(metric_arg)
        if metric is _UNRESOLVED:
            out.append((node.lineno,
                        "metric references an undeclared module "
                        "constant"))
            continue
        if metric is None:
            continue  # dynamic: SLORule's runtime validation covers it
        if metric in _telemetry.CANONICAL_METRIC_NAMES:
            continue
        prefix = _telemetry.HEALTH_METRIC_PREFIX
        if (metric.startswith(prefix)
                and metric[len(prefix):] in _HEALTH_EVENT_VALUES):
            continue
        out.append((node.lineno, f"undeclared metric {metric!r}"))
    return out


#: Sections of a windowed snapshot / federation delta frame that are
#: keyed by metric name — a lookup into one with a typo'd name silently
#: returns None forever, exactly the failure mode slo-metrics exists to
#: catch (the federated fold made these lookups a public idiom:
#: autoscaler, exporter, and watchdog all read them).
_FRAME_SECTIONS = frozenset({"histograms", "counters", "gauges"})


def _declared_metric(name: str) -> bool:
    if name in _telemetry.CANONICAL_METRIC_NAMES:
        return True
    prefix = _telemetry.HEALTH_METRIC_PREFIX
    return (name.startswith(prefix)
            and name[len(prefix):] in _HEALTH_EVENT_VALUES)


def bad_frame_metric_keys(tree: ast.AST) -> List[Tuple[int, str]]:
    """(lineno, reason) for metric-name lookups into a windowed
    snapshot or federation delta-frame section —
    ``X["histograms"].get(<name>)`` and
    ``view.attribution(<metric>, ...)`` — whose name does not
    statically resolve to a declared metric."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not isinstance(f, ast.Attribute):
            continue
        key_arg = None
        what = None
        if (f.attr == "get" and node.args
                and isinstance(f.value, ast.Subscript)
                and isinstance(f.value.slice, ast.Constant)
                and f.value.slice.value in _FRAME_SECTIONS):
            key_arg = node.args[0]
            what = f"[{f.value.slice.value!r}].get() metric key"
        elif f.attr == "attribution":
            key_arg = node.args[0] if node.args else None
            for kw in node.keywords:
                if kw.arg == "metric":
                    key_arg = kw.value
            what = "attribution() metric"
        if key_arg is None:
            continue
        name = _resolve_string_expr(key_arg)
        if name is _UNRESOLVED:
            out.append((node.lineno,
                        f"{what} references an undeclared module "
                        "constant"))
        elif name is not None and not _declared_metric(name):
            out.append((node.lineno,
                        f"{what}: undeclared metric {name!r}"))
    return out


@register
class SLOMetricsRule(Rule):
    id = "slo-metrics"
    title = "SLO rule metrics and frame keys must resolve to declared names"
    rationale = (
        "A typo'd metric watches nothing forever. SLORule's runtime "
        "validation catches dynamic cases; this rule catches literals "
        "and module-constant concatenations before any scope ever "
        "runs — including a typo'd MODULE CONSTANT, which would "
        "otherwise only surface at import time. The same discipline "
        "covers reads: a metric-name lookup into a windowed snapshot "
        "or federation delta frame (X['histograms'].get(name), "
        "view.attribution(metric, ...)) silently returns None on a "
        "typo, so those keys must resolve too.")

    def check(self, src: SourceFile) -> List[Finding]:
        found = [self.finding(
            src, line,
            f"SLO rule metric: {reason} — must be a "
            "CANONICAL_METRIC_NAMES entry or a sparkdl.health.<event> "
            "mirror of a core/health.py constant")
            for line, reason in bad_slo_rule_metrics(src.tree)]
        found.extend(self.finding(
            src, line,
            f"windowed-metrics lookup: {reason} — frame and snapshot "
            "sections are keyed by declared metric names")
            for line, reason in bad_frame_metric_keys(src.tree))
        return found


# ---------------------------------------------------------------------------
# atomic-write (ISSUE 11)
# ---------------------------------------------------------------------------

# Modules whose on-disk artifacts must survive kill -9: the durable
# journal, checkpoint manifests, baseline stores, telemetry reports.
_STATE_PERSISTING = {"durability.py", "checkpoint.py", "baseline.py",
                     "telemetry.py"}


def _expr_mentions_tmp(node: ast.AST) -> bool:
    """True when the path expression visibly routes through a temp name
    (``tmp`` in an identifier, attribute, or string literal) — the
    write-to-tmp half of the tmp + ``os.replace`` idiom."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and "tmp" in sub.id.lower():
            return True
        if isinstance(sub, ast.Attribute) and "tmp" in sub.attr.lower():
            return True
        if (isinstance(sub, ast.Constant) and isinstance(sub.value, str)
                and "tmp" in sub.value.lower()):
            return True
    return False


@register
class AtomicWriteRule(Rule):
    id = "atomic-write"
    title = "Durable state must be written tmp-then-os.replace, never in place"
    rationale = (
        "A crash (or injected kill -9) midway through an in-place "
        "open(path, 'w') leaves a torn file that a restart then trusts. "
        "State-persisting modules must write to a tmp path, fsync, and "
        "publish with os.replace so readers only ever see complete "
        "artifacts.")

    def check(self, src: SourceFile) -> List[Finding]:
        if pathlib.PurePath(src.rel).name not in _STATE_PERSISTING:
            return []
        out: List[Finding] = []
        for node in ast.walk(src.tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "open"
                    and len(node.args) >= 2):
                continue
            mode = node.args[1]
            if not (isinstance(mode, ast.Constant)
                    and isinstance(mode.value, str)
                    and "w" in mode.value):
                continue  # reads, appends, r+b: not in-place publishes
            if _expr_mentions_tmp(node.args[0]):
                continue
            out.append(self.finding(
                src, node.lineno,
                f"open(..., {mode.value!r}) writes durable state in "
                "place — a crash mid-write leaves a torn file; write to "
                "a tmp path and os.replace it over the destination"))
        return out


# ---------------------------------------------------------------------------
# tenant-tag (ISSUE 16)
# ---------------------------------------------------------------------------

#: The online plane: every serving request is SOME tenant's request.
#: Batch callers (ml/engine/...) inherit the ambient tenant_scope or
#: the EngineConfig default, so only serving/ is in scope — an online
#: request with no tag burns the shared "default" lane's quota, which
#: under deficit-round-robin lets one client starve the rest invisibly.
TENANT_SCOPES = ("serving",)


#: Serving-plane dispatch entry points the tenant tag must ride
#: through. ``execute`` is the single-process choke point;
#: ``submit_predict`` is the cluster serving router's wire dispatch
#: (serving/cluster.py) — a routed predict that drops the tag would
#: burn the default lane's quota on the WORKER, invisibly to the
#: coordinator's per-tenant series.
_TENANT_DISPATCH_NAMES = ("execute", "submit_predict")


def untagged_execute_calls(tree: ast.AST) -> List[int]:
    """Lines of ``executor.execute(...)`` / bare ``execute(...)`` /
    ``submit_predict(...)`` (bare or as a method) calls with neither a
    ``tenant=`` keyword nor a ``**kwargs`` spread (a spread may carry
    the tag; it is not statically checkable and is skipped, same
    stance as dynamic span names)."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        is_dispatch = (
            (isinstance(f, ast.Attribute) and f.attr == "execute"
             and isinstance(f.value, ast.Name)
             and f.value.id == "executor")
            or (isinstance(f, ast.Name)
                and f.id in _TENANT_DISPATCH_NAMES)
            or (isinstance(f, ast.Attribute)
                and f.attr == "submit_predict"))
        if not is_dispatch:
            continue
        kw_names = {kw.arg for kw in node.keywords}
        if "tenant" in kw_names or None in kw_names:
            continue
        out.append(node.lineno)
    return sorted(out)


@register
class TenantTagRule(Rule):
    id = "tenant-tag"
    title = "serving-plane executor.execute() must carry a tenant tag"
    rationale = (
        "The executor's fair-queueing coalescer arbitrates by tenant "
        "(deficit-round-robin within each priority lane, "
        "docs/RESILIENCE.md 'Per-tenant fair queueing'): an online "
        "request submitted without `tenant=` lands in the shared "
        "default lane, where one client's flood starves every other "
        "untagged client with no per-tenant metric series to show it. "
        "The serving plane must thread its caller's tag — even "
        "`tenant=None` (resolve via the ambient scope) is an explicit, "
        "visible decision.")

    def check(self, src: SourceFile) -> List[Finding]:
        parts = set(pathlib.PurePath(src.rel).parts)
        if not parts & set(TENANT_SCOPES):
            return []
        return [self.finding(
            src, line,
            "serving-plane dispatch (executor.execute / "
            "submit_predict) without a tenant= argument — the request "
            "burns the shared default lane's fair-queueing quota; "
            "thread the caller's tenant tag (tenant=None to adopt the "
            "ambient tenant_scope)")
            for line in untagged_execute_calls(src.tree)]


# ---------------------------------------------------------------------------
# columnar-hot-path (ISSUE 18)
# ---------------------------------------------------------------------------

#: The data-plane modules where image/tensor columns flow decode →
#: device. param/ (loader plumbing) and serving/ (row-level requests)
#: are out of scope; their payloads are single rows by design.
COLUMNAR_SCOPES = ("image", "ml", "engine")

#: Per-row wrappers whose appearance inside a loop/comprehension means
#: an image or tensor column is being rebuilt one Python dict at a time.
_PER_ROW_IMAGE_WRAPPERS = ("imageArrayToStruct",)


def per_row_column_hops(tree: ast.AST) -> List[Tuple[int, str]]:
    """(lineno, reason) for per-row hops over columnar data: any
    ``.to_pylist()`` call, and any per-row image-struct construction
    (``imageArrayToStruct``) under a loop or comprehension."""
    out = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr == "to_pylist":
            out.add((node.lineno,
                     ".to_pylist() materializes the column as per-row "
                     "Python objects"))
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.ListComp, ast.SetComp,
                                 ast.DictComp, ast.GeneratorExp)):
            continue
        for sub in ast.walk(loop):
            if not isinstance(sub, ast.Call):
                continue
            f = sub.func
            name = (f.attr if isinstance(f, ast.Attribute)
                    else f.id if isinstance(f, ast.Name) else None)
            if name in _PER_ROW_IMAGE_WRAPPERS:
                out.add((sub.lineno,
                         f"per-row {name}() in a loop rebuilds the "
                         "image column one Python dict at a time"))
    return sorted(out)


@register
class ColumnarHotPathRule(Rule):
    id = "columnar-hot-path"
    title = "image/tensor columns must stay columnar on the data plane"
    rationale = (
        "The ingest spine is zero-copy columnar end to end (docs/PERF.md "
        "'Columnar data plane'): decode-pool segments become Arrow "
        "binary children become device uint8 batches with no per-row "
        "Python hop. A `.to_pylist()` or loop of `imageArrayToStruct` on "
        "that route silently reintroduces the per-row dict "
        "materialization BENCH_r05 measured at two orders of magnitude "
        "of lost throughput — and no test fails, only the trajectory. "
        "String/URI/label columns and ragged-batch fallbacks are "
        "legitimate: suppress those sites with a reason.")

    def check(self, src: SourceFile) -> List[Finding]:
        parts = set(pathlib.PurePath(src.rel).parts)
        if not parts & set(COLUMNAR_SCOPES):
            return []
        return [self.finding(
            src, line,
            f"{reason} — on the columnar data plane "
            "(image/, ml/, engine/) use the zero-copy views "
            "(arrowImageBatch, list_column_to_numpy, to_numpy with "
            "validity masks) or suppress with the ragged/string-column "
            "justification")
            for line, reason in per_row_column_hops(src.tree)]
