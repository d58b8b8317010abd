"""The six one-off AST lints (ISSUEs 2–7), now thin wrappers over the
shared analysis framework (ISSUE 8).

Each lint lives as a registered rule in ``sparkdl_tpu/analysis/lints.py``
— one engine, one suppression syntax (``# sparkdl: allow(<rule>):
<why>``), one catalog (docs/ANALYSIS.md). The package-wide tests here
invoke the analyzer per rule (so suppressions work exactly as in the
CLI); each self-test seeds the original violation shape through the
framework and asserts the registered rule still flags it — the
typo/self-test coverage the standalone lints had is preserved
verbatim. The full-catalog gate (every rule at once, plus the
concurrency pack) is ``tests/test_analysis.py``.
"""

import ast
import pathlib

import pytest

from sparkdl_tpu import analysis
from sparkdl_tpu.analysis import framework, lints
from sparkdl_tpu.core import health as _health
from sparkdl_tpu.core import telemetry as _telemetry

ROOT = pathlib.Path(__file__).resolve().parent.parent / "sparkdl_tpu"


def _package_findings(rule_id):
    """Run ONE rule over the package through the framework (inline
    suppressions apply, the shipped empty baseline does not matter)."""
    return analysis.analyze(paths=[ROOT], rule_ids=[rule_id]).findings


def _seed(rule_id, source, rel="seed.py"):
    """Seed a violation through the framework; the registered rule must
    flag it (lines returned sorted)."""
    src = framework.SourceFile.from_source(source, rel=rel)
    res = analysis.analyze_sources([src], rule_ids=[rule_id])
    return sorted(f.line for f in res.findings)


# ---------------------------------------------------------------------------
# broad-retry (ISSUE 2)
# ---------------------------------------------------------------------------


def test_no_blind_broad_retry_loops():
    offenders = _package_findings("broad-retry")
    assert not offenders, (
        "broad except inside a loop without re-raise or "
        "core.resilience.classify — blind retry would replay FATAL "
        "errors. Route the handler through resilience.classify, or mark "
        "a deliberate non-retry swallow with "
        "'# sparkdl: allow(broad-retry): <reason>': "
        f"{[str(f) for f in offenders]}")


def test_lint_catches_the_old_blind_retry_shape():
    """Self-test: the pre-supervision `_run_partition` loop (retry every
    failure blindly) must trip the registered rule."""
    bad = (
        "def run(ops, batch):\n"
        "    for attempt in range(3):\n"
        "        try:\n"
        "            return ops(batch)\n"
        "        except Exception as e:\n"
        "            last = e\n"
    )
    assert _seed("broad-retry", bad) == [5]


# ---------------------------------------------------------------------------
# blocking-fetch-in-fit (ISSUE 3)
# ---------------------------------------------------------------------------


def test_trainer_step_loop_has_no_blocking_device_fetch():
    # vacuity guard: the rule only fires on files defining Trainer.fit,
    # so prove trainer.py still has one (with loops) before trusting a
    # clean package run
    tree = ast.parse((ROOT / "train" / "trainer.py").read_text())
    fits = [item for node in ast.walk(tree)
            if isinstance(node, ast.ClassDef) and node.name == "Trainer"
            for item in node.body
            if isinstance(item, ast.FunctionDef) and item.name == "fit"]
    assert fits, "Trainer.fit not found"
    assert any(isinstance(n, (ast.For, ast.While))
               for n in ast.walk(fits[0])), "Trainer.fit has no step loop?"
    offenders = _package_findings("blocking-fetch-in-fit")
    assert not offenders, (
        "blocking device fetch inside Trainer.fit's step loop — "
        "int()/float()/np.asarray/jax.device_get/block_until_ready "
        "there re-serialize the async input pipeline. Move the fetch "
        "into the designated sync helpers (sync/save_checkpoint): "
        f"{[str(f) for f in offenders]}")


def test_lint_catches_the_old_per_step_sync_shape():
    """Self-test: the pre-pipeline loop body (`step = int(state.step)`
    per step, plus a device_get checkpoint fetch) must trip the rule —
    while helper DEFINITIONS (pre-loop or even inside the loop) stay
    exempt: only their call-sites block."""
    bad = (
        "class Trainer:\n"
        "    def fit(self, state, batches):\n"
        "        def sync(st):\n"
        "            return int(st.step)\n"  # pre-loop helper: exempt
        "        for x, y in batches:\n"
        "            def fetch():\n"
        "                return int(state.step)\n"  # nested DEF: exempt
        "            state, m = step(state, x, y)\n"
        "            step_n = int(state.step)\n"  # line 9: violation
        "            ckpt.save(step_n, jax.device_get(state))\n"  # line 10
        "        return state\n"
    )
    assert _seed("blocking-fetch-in-fit", bad) == [9, 10]


# ---------------------------------------------------------------------------
# span-names (ISSUE 4)
# ---------------------------------------------------------------------------


def test_every_span_name_is_canonical():
    offenders = _package_findings("span-names")
    assert not offenders, (
        "span/phase name not declared in "
        "core.telemetry.CANONICAL_SPAN_NAMES — a typo'd name silently "
        "forks a timer and a trace track. Add the name to the catalog "
        f"(and docs/OBSERVABILITY.md) or fix the typo: "
        f"{[str(f) for f in offenders]}")


def test_span_name_lint_catches_typo_and_resolves_constants():
    """Self-test: a typo'd literal trips the rule; module-constant names
    resolve to their canonical strings and pass."""
    bad = (
        "from sparkdl_tpu.core import profiling, telemetry\n"
        "with profiling.annotate('sparkdl.train_stepp'):\n"  # typo
        "    pass\n"
        "with telemetry.span(telemetry.SPAN_FIT):\n"         # constant
        "    pass\n"
        "with profiling.annotate(profiling.STAGE_BATCH):\n"  # constant
        "    pass\n"
        "with telemetry.span(dynamic_name):\n"               # skipped
        "    pass\n"
    )
    assert _seed("span-names", bad) == [2]
    # the resolution helper still sees all three checkable names
    names = lints.span_names_in(ast.parse(bad))
    assert ("sparkdl.train_stepp", 2) in names
    assert ("sparkdl.fit", 4) in names
    assert ("sparkdl.stage_batch", 6) in names
    assert len(names) == 3  # the dynamic name is not checkable
    assert "sparkdl.train_stepp" not in _telemetry.CANONICAL_SPAN_NAMES


@pytest.mark.parametrize("helper, name", [
    ("model_build", _telemetry.SPAN_MODEL_BUILD),
    ("compile_span", _telemetry.SPAN_COMPILE),
])
def test_startup_helpers_open_canonical_spans(helper, name):
    """ISSUE 41: the start-up record's two spans go through
    ``profiling.annotate`` with catalogued names, so the lint sees them."""
    assert name in _telemetry.CANONICAL_SPAN_NAMES
    tree = ast.parse((ROOT / "core" / "profiling.py").read_text())
    body = next(n for n in ast.walk(tree)
                if isinstance(n, ast.FunctionDef) and n.name == helper)
    assert name in {found for found, _ in lints.span_names_in(body)}


@pytest.mark.parametrize("key", _telemetry.STARTUP_KEYS)
def test_startup_gauges_are_catalogued(key):
    """Every key of the start-up record is a declared gauge — an SLO rule
    may watch it, and one with a histogram's stat is refused."""
    from sparkdl_tpu.core import profiling, slo

    name = _telemetry.STARTUP_METRIC_PREFIX + key
    assert _telemetry.CANONICAL_METRIC_KINDS[name] == "gauge"
    assert key in profiling.startup_stats()
    slo.SLORule(f"startup-{key}", metric=name, window_s=30.0,
                threshold=60.0, stat="value")
    with pytest.raises(ValueError):
        slo.SLORule(f"startup-{key}-p99", metric=name, window_s=30.0,
                    threshold=60.0, stat="p99")


# ---------------------------------------------------------------------------
# executor-choke-point (ISSUE 5)
# ---------------------------------------------------------------------------


def test_featurize_route_enters_device_via_executor_choke_point():
    offenders = _package_findings("executor-choke-point")
    assert not offenders, (
        "direct apply_batch/jitted call on the engine featurize route — "
        "device entry must go through core.executor.execute (the "
        "coalescing choke point), or concurrent partitions silently "
        "regress to per-partition launches (docs/PERF.md "
        "'Cross-partition coalescing'): "
        f"{[str(f) for f in offenders]}")


def test_choke_point_lint_catches_direct_apply_batch():
    """Self-test: the pre-executor transformer shape (calling the model's
    apply_batch / jitted straight from the partition op) must trip —
    when the file lives on the guarded route (ml/)."""
    bad = (
        "def apply_partition(batch):\n"
        "    out = model.apply_batch(stacked, batch_size=64)\n"
        "    fn = model.jitted(mesh=mesh)\n"
        "    good = device_executor.execute(model, stacked)\n"
        "    return out\n"
    )
    assert _seed("executor-choke-point", bad, rel="ml/seed.py") == [2, 3]
    # the model layer and training path stay out of scope by path
    assert _seed("executor-choke-point", bad, rel="core/seed.py") == []


# ---------------------------------------------------------------------------
# health-constants (ISSUE 6)
# ---------------------------------------------------------------------------


def test_every_health_record_uses_a_declared_constant():
    offenders = _package_findings("health-constants")
    assert not offenders, (
        "health.record() call site not using a constant declared in "
        "core/health.py — a typo'd or ad-hoc event name silently forks "
        "a counter outside the docs catalog and the telemetry mirror. "
        f"Declare the event and reference it: "
        f"{[str(f) for f in offenders]}")


def test_health_record_lint_catches_typos_and_bare_strings():
    """Self-test: a bare string event, a typo'd constant, and a local
    variable all trip; a declared constant passes."""
    bad = (
        "from sparkdl_tpu.core import health\n"
        "health.record('task_retried', partition=1)\n"      # bare string
        "health.record(health.TASK_RETIRED)\n"              # typo'd name
        "health.record(evt, partition=1)\n"                 # dynamic name
        "health.record(health.TASK_RETRIED, partition=1)\n"  # ok
        "mon.record('whatever')\n"                          # not the hook
    )
    assert _seed("health-constants", bad) == [2, 3, 4]
    flagged = lints.bad_health_record_calls(ast.parse(bad))
    assert "TASK_RETIRED" in flagged[1][1]
    # the constants set is non-trivial and holds the canonical events
    assert "TASK_RETRIED" in lints.HEALTH_EVENT_CONSTANTS
    assert "BREAKER_OPEN" in lints.HEALTH_EVENT_CONSTANTS


# ---------------------------------------------------------------------------
# slo-metrics (ISSUE 7)
# ---------------------------------------------------------------------------


def test_every_slo_rule_metric_is_declared():
    # the rule is not vacuous: slo.py really constructs rules
    slo_tree = ast.parse((ROOT / "core" / "slo.py").read_text())
    assert any(isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
               and n.func.id == "SLORule" for n in ast.walk(slo_tree))
    offenders = _package_findings("slo-metrics")
    assert not offenders, (
        "SLO rule metric not declared in core.telemetry."
        "CANONICAL_METRIC_NAMES (or as a sparkdl.health.<event> mirror "
        "of a core/health.py constant) — a typo'd metric watches "
        f"nothing forever: {[str(f) for f in offenders]}")


def test_slo_metric_lint_catches_typos_and_resolves_constants():
    """Self-test: a typo'd literal and a typo'd module constant both
    trip; canonical literals, module constants and prefix
    concatenations pass; a local variable is left to the runtime
    check."""
    bad = (
        "from sparkdl_tpu.core import health, telemetry\n"
        "from sparkdl_tpu.core.slo import SLORule\n"
        "SLORule('a', metric='sparkdl.executor.queue_wait_ss',\n"  # typo
        "        window_s=1.0, threshold=1.0)\n"
        "SLORule('b', metric=telemetry.M_QUEUE_WAIT_S,\n"          # ok
        "        window_s=1.0, threshold=1.0)\n"
        "SLORule('c', metric=telemetry.HEALTH_METRIC_PREFIX\n"     # ok
        "        + health.EXECUTOR_SHED,\n"
        "        window_s=1.0, threshold=1.0)\n"
        "SLORule('d', metric=telemetry.HEALTH_METRIC_PREFIX\n"     # typo'd
        "        + health.EXECUTOR_SHEDD,\n"                       # constant
        "        window_s=1.0, threshold=1.0)\n"
        "SLORule('e', metric=some_variable,\n"                     # dynamic
        "        window_s=1.0, threshold=1.0)\n"
        "SLORule('f', 'sparkdl.health.not_an_event',\n"            # bad
        "        1.0, 1.0)\n"                                      # mirror
    )
    assert _seed("slo-metrics", bad) == [3, 10, 15]
    flagged = lints.bad_slo_rule_metrics(ast.parse(bad))
    assert "queue_wait_ss" in flagged[0][1]
    assert "undeclared module constant" in flagged[1][1]
    assert "not_an_event" in flagged[2][1]
    # the shipped default rules resolve through exactly these paths
    assert "sparkdl.health.executor_shed" not in \
        _telemetry.CANONICAL_METRIC_NAMES
    assert "executor_shed" in {
        getattr(_health, name) for name in lints.HEALTH_EVENT_CONSTANTS}


# ---------------------------------------------------------------------------
# tenant-tag (ISSUE 16)
# ---------------------------------------------------------------------------


def test_serving_plane_always_tags_executor_calls():
    # the rule is not vacuous: the serving plane really calls
    # executor.execute (the predict path and the shadow leg)
    server_tree = ast.parse(
        (ROOT / "serving" / "server.py").read_text())
    assert len(lints.untagged_execute_calls(server_tree)) == 0
    calls = [n for n in ast.walk(server_tree)
             if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute)
             and n.func.attr == "execute"]
    assert len(calls) >= 2, "serving plane stopped calling the executor?"
    offenders = _package_findings("tenant-tag")
    assert not offenders, (
        "serving-plane executor.execute() without a tenant= argument — "
        "the request burns the shared default lane's deficit-round-robin "
        "quota and vanishes from the per-tenant queue-wait series. "
        f"Thread the caller's tenant tag: {[str(f) for f in offenders]}")


def test_tenant_tag_lint_catches_untagged_serving_calls():
    """Self-test: an untagged serving-plane execute trips; an explicit
    tag — even ``tenant=None`` — passes, a ``**kwargs`` spread is not
    statically checkable and passes, and the batch route (ml/) stays
    out of scope by path."""
    bad = (
        "from sparkdl_tpu.core import executor\n"
        "def predict(model, batch, kw):\n"
        "    a = executor.execute(model, batch, batch_size=1)\n"  # bad
        "    b = execute(model, batch, batch_size=1)\n"           # bad
        "    c = executor.execute(model, batch, tenant='acme')\n"  # ok
        "    d = executor.execute(model, batch, tenant=None)\n"    # ok
        "    e = executor.execute(model, batch, **kw)\n"           # spread
        "    return a, b, c, d, e\n"
    )
    assert _seed("tenant-tag", bad, rel="serving/seed.py") == [3, 4]
    # the batch/featurize route resolves its tenant ambiently — out of
    # scope by path, same scoping mechanism as executor-choke-point
    assert _seed("tenant-tag", bad, rel="ml/seed.py") == []


def test_tenant_tag_suppression_works():
    bad = (
        "from sparkdl_tpu.core import executor\n"
        "def probe(model, batch):\n"
        "    return executor.execute(model, batch)"
        "  # sparkdl: allow(tenant-tag): synthetic warmup probe, "
        "not client traffic\n"
    )
    src = framework.SourceFile.from_source(bad, rel="serving/seed.py")
    res = analysis.analyze_sources([src], rule_ids=["tenant-tag"])
    assert not res.findings
    assert len(res.suppressed) == 1
