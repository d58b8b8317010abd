"""The static-analysis subsystem (ISSUE 8): tier-1 gate + framework
self-tests.

``test_analyzer_clean_on_package`` is the gate: the FULL rule catalog
(concurrency discipline + the migrated lints + suppression hygiene)
runs over ``sparkdl_tpu/`` and must report zero unsuppressed findings —
every future PR passes through it via the tier-1 command. The rest
pins the framework contract: suppression grammar (wrong rule name or a
missing justification does not suppress), baseline round-trip, CLI exit
codes (0 clean / 1 findings / 2 usage), the ``--json`` schema, and a
fixture package under ``tests/fixtures/analysis/`` seeding one
violation per registered rule so no rule can go silently inert.
"""

import json
import pathlib

from sparkdl_tpu import analysis
from sparkdl_tpu.analysis import baseline as baseline_mod
from sparkdl_tpu.analysis import cli, framework

REPO = pathlib.Path(__file__).resolve().parent.parent
PACKAGE = REPO / "sparkdl_tpu"
FIXTURES = REPO / "tests" / "fixtures" / "analysis"


# ---------------------------------------------------------------------------
# The tier-1 gate
# ---------------------------------------------------------------------------


def test_analyzer_clean_on_package():
    """`python -m sparkdl_tpu.analysis` must exit 0 on the repo: every
    hazard is fixed or carries a justified inline suppression."""
    res = analysis.analyze(paths=[PACKAGE])
    listing = "\n".join(str(f) for f in res.findings)
    assert not res.findings, (
        "unsuppressed analyzer findings in sparkdl_tpu/ — fix the "
        "hazard or add '# sparkdl: allow(<rule>): <why>' with a real "
        f"justification (docs/ANALYSIS.md):\n{listing}")
    # the run is not vacuous: it saw the whole package and the known
    # intentional patterns arrived as justified suppressions
    assert res.files > 50
    assert len(res.suppressed) >= 5
    assert all(why for _f, why in res.suppressed)


def test_every_package_suppression_is_justified():
    """No bare `allow(...)` anywhere in the tree (the hygiene rule
    enforces this at analyze time; this pins it directly)."""
    sources = analysis.collect_sources([PACKAGE])
    sups = [(src.rel, sup) for src in sources
            for sup in src.suppressions()]
    assert sups, "expected at least one suppression in the tree"
    for rel, sup in sups:
        assert sup.justification, (
            f"{rel}:{sup.line}: suppression without a justification")


def test_shipped_baseline_is_empty():
    """Policy: fix or suppress inline; the baseline is for emergencies
    and ships empty (zero unexplained baseline entries)."""
    data = json.loads(baseline_mod.DEFAULT_BASELINE_PATH.read_text())
    assert data["entries"] == []


# ---------------------------------------------------------------------------
# Fixture package: one seeded violation per registered rule
# ---------------------------------------------------------------------------

EXPECTED_FIXTURE_RULES = {
    "lock_order_cycle.py": {"lock-order"},
    "wait_foreign_lock.py": {"wait-holding-lock"},
    "blocking_under_lock.py": {"blocking-under-lock"},
    "unguarded_write.py": {"unguarded-shared-write"},
    "thread_lifecycle.py": {"thread-lifecycle"},
    "process_lifecycle.py": {"thread-lifecycle"},
    "broad_retry.py": {"broad-retry"},
    "ml/choke_point.py": {"executor-choke-point"},
    "ml/precision_donation.py": {"executor-choke-point"},
    "ml/row_hop.py": {"columnar-hot-path"},
    "serving/hot_path.py": {"executor-choke-point"},
    "serving/untagged_execute.py": {"tenant-tag"},
    "serving/untagged_cluster_dispatch.py": {"tenant-tag"},
    "cluster/worker_loop.py": {"executor-choke-point",
                               "thread-lifecycle"},
    "trainer_fetch.py": {"blocking-fetch-in-fit"},
    "span_name_typo.py": {"span-names"},
    "remote_span_name.py": {"span-names"},
    "health_bare_string.py": {"health-constants"},
    "slo_metric_typo.py": {"slo-metrics"},
    "federated_frame_key.py": {"slo-metrics"},
    "state/durability.py": {"atomic-write"},
    "suppression_no_reason.py": {"blocking-under-lock",
                                 "suppression-hygiene"},
}


def _fixture_name(path: str) -> str:
    parts = pathlib.PurePath(path).parts
    return "/".join(parts[parts.index("analysis") + 1:])


def test_fixture_package_seeds_every_rule():
    res = analysis.analyze(paths=[FIXTURES])
    got = {}
    for f in res.findings:
        got.setdefault(_fixture_name(f.path), set()).add(f.rule)
    assert got == EXPECTED_FIXTURE_RULES
    # every registered rule is exercised by at least one fixture — a
    # rule that stops firing on its own seeded violation fails HERE,
    # not silently in some future review
    flagged = set().union(*got.values())
    assert set(analysis.all_rules()) <= flagged


# ---------------------------------------------------------------------------
# Suppression grammar
# ---------------------------------------------------------------------------

_SLEEP_UNDER_LOCK = (
    "import threading\n"
    "import time\n"
    "_lock = threading.Lock()\n"
    "def tick():\n"
    "    with _lock:\n"
    "        time.sleep(0.1){comment}\n"
)


def _run(source: str, rule_ids=None, rel: str = "mem.py"):
    src = framework.SourceFile.from_source(source, rel=rel)
    return analysis.analyze_sources([src], rule_ids=rule_ids)


def test_justified_suppression_suppresses():
    res = _run(_SLEEP_UNDER_LOCK.format(
        comment="  # sparkdl: allow(blocking-under-lock): test lock is "
                "single-threaded"))
    assert not res.findings
    assert len(res.suppressed) == 1
    finding, why = res.suppressed[0]
    assert finding.rule == "blocking-under-lock"
    assert why == "test lock is single-threaded"


def test_wrong_rule_name_does_not_suppress():
    res = _run(_SLEEP_UNDER_LOCK.format(
        comment="  # sparkdl: allow(broad-retry): wrong rule entirely"))
    assert [f.rule for f in res.findings] == ["blocking-under-lock"]
    assert not res.suppressed


def test_missing_justification_does_not_suppress_and_is_flagged():
    res = _run(_SLEEP_UNDER_LOCK.format(
        comment="  # sparkdl: allow(blocking-under-lock)"))
    assert {f.rule for f in res.findings} == {"blocking-under-lock",
                                             "suppression-hygiene"}


def test_unknown_rule_in_suppression_is_flagged():
    res = _run("x = 1  # sparkdl: allow(no-such-rule): because\n")
    assert [f.rule for f in res.findings] == ["suppression-hygiene"]
    assert "no-such-rule" in res.findings[0].message


def test_unrecognized_directive_is_flagged():
    res = _run("x = 1  # sparkdl: alow(broad-retry): typo'd verb\n")
    assert [f.rule for f in res.findings] == ["suppression-hygiene"]


def test_stacked_comment_only_directives_target_the_same_statement():
    """Comment-only directives skip over further comment lines to the
    next CODE line — a directive stacked above another comment must not
    silently target the comment and suppress nothing."""
    source = (
        "import threading\n"
        "import time\n"
        "_lock = threading.Lock()\n"
        "def t():\n"
        "    with _lock:\n"
        "        # sparkdl: allow(blocking-under-lock): io is the point\n"
        "        # sparkdl: allow(unguarded-shared-write): stacked, inert\n"
        "        # an ordinary explanatory comment in between\n"
        "        time.sleep(0.1)\n"
    )
    src = framework.SourceFile.from_source(source)
    assert [s.target for s in src.suppressions()] == [9, 9]
    res = analysis.analyze_sources([src])
    assert not res.findings
    assert len(res.suppressed) == 1  # the sleep; the second is inert


def test_comment_only_line_suppresses_the_next_line():
    source = (
        "import threading\n"
        "import time\n"
        "_lock = threading.Lock()\n"
        "def tick():\n"
        "    with _lock:\n"
        "        # sparkdl: allow(blocking-under-lock): multi-line "
        "statement below\n"
        "        time.sleep(\n"
        "            0.1)\n"
    )
    res = _run(source)
    assert not res.findings
    assert len(res.suppressed) == 1


def test_docstring_mention_is_not_a_directive():
    """Only COMMENT tokens parse as directives — prose/docstrings
    describing the syntax must not trip hygiene (or suppress)."""
    source = (
        '"""Write `# sparkdl: allow(rule): why` to suppress.\n'
        "\n"
        "Also mentions # sparkdl: allow(broad-retry) mid-text.\n"
        '"""\n'
        "x = 1\n"
    )
    res = _run(source)
    assert not res.findings


# ---------------------------------------------------------------------------
# Baseline round-trip
# ---------------------------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    res = analysis.analyze(paths=[FIXTURES])
    assert res.findings
    path = tmp_path / "baseline.json"
    grandfatherable_in = [f for f in res.findings
                          if f.rule != "suppression-hygiene"]
    baseline_mod.Baseline.from_findings(grandfatherable_in).save(path)

    loaded = baseline_mod.Baseline.load(path)
    res2 = analysis.analyze(paths=[FIXTURES], baseline=loaded)
    # everything grandfatherable is absorbed; hygiene findings are
    # NEVER baselineable (a one-command bypass of the justification
    # requirement otherwise) and keep firing
    assert {f.rule for f in res2.findings} == {"suppression-hygiene"}
    grandfatherable = [f for f in res.findings
                       if f.rule != "suppression-hygiene"]
    assert len(res2.baselined) == len(grandfatherable)
    assert not res2.stale_baseline


def test_baseline_matching_survives_line_shifts(tmp_path):
    """Messages embed 'acquired line N' context; the baseline key
    normalizes those so an unrelated edit shifting the file doesn't
    churn the baseline."""
    bad = (FIXTURES / "blocking_under_lock.py").read_text()
    res = analysis.analyze_sources(
        [framework.SourceFile.from_source(bad, rel="shifty.py")],
        rule_ids=["blocking-under-lock"])
    bl = baseline_mod.Baseline.from_findings(res.findings)
    shifted = "# a new leading comment shifts every line\n" + bad
    res2 = analysis.analyze_sources(
        [framework.SourceFile.from_source(shifted, rel="shifty.py")],
        rule_ids=["blocking-under-lock"], baseline=bl)
    assert not res2.findings
    assert len(res2.baselined) == 1
    assert not res2.stale_baseline


def test_baseline_stale_entries_are_surfaced(tmp_path):
    res = analysis.analyze(paths=[FIXTURES])
    stale_entry = {"rule": "broad-retry", "path": "deleted_file.py",
                   "message": "no longer exists"}
    bl = baseline_mod.Baseline(
        [f.as_dict() for f in res.findings
         if f.rule != "suppression-hygiene"] + [stale_entry])
    res2 = analysis.analyze(paths=[FIXTURES], baseline=bl)
    assert {f.rule for f in res2.findings} == {"suppression-hygiene"}
    assert res2.stale_baseline == [stale_entry]


def test_baseline_load_missing_file_is_empty(tmp_path):
    bl = baseline_mod.Baseline.load(tmp_path / "absent.json")
    assert bl.entries == []


# ---------------------------------------------------------------------------
# CLI: exit codes + --json schema
# ---------------------------------------------------------------------------


def test_cli_exit_0_on_clean_tree(capsys):
    assert cli.main([str(PACKAGE)]) == 0
    out = capsys.readouterr().out
    assert "0 finding(s)" in out


def test_cli_exit_1_on_findings(capsys):
    assert cli.main([str(FIXTURES), "--no-baseline"]) == 1
    assert "[broad-retry]" in capsys.readouterr().out


def test_cli_exit_2_on_unknown_rule(capsys):
    assert cli.main([str(FIXTURES), "--rule", "no-such-rule"]) == 2
    assert "no-such-rule" in capsys.readouterr().err


def test_cli_exit_2_on_missing_path(capsys):
    assert cli.main(["/no/such/path/anywhere"]) == 2


def test_cli_json_schema(capsys):
    assert cli.main([str(FIXTURES), "--json", "--no-baseline"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["version"] == 1
    assert set(doc) >= {"version", "rules", "files", "findings",
                        "suppressed", "counts", "stale_baseline"}
    assert doc["counts"]["findings"] == len(doc["findings"]) > 0
    for f in doc["findings"]:
        assert set(f) == {"rule", "path", "line", "message"}
        assert isinstance(f["line"], int)
    assert set(doc["rules"]) == set(analysis.all_rules()) | {
        framework.SUPPRESSION_HYGIENE}


def test_cli_rule_filter(capsys):
    assert cli.main([str(FIXTURES), "--rule", "broad-retry",
                     "--json", "--no-baseline"]) == 1
    doc = json.loads(capsys.readouterr().out)
    assert {f["rule"] for f in doc["findings"]} == {"broad-retry"}


def test_cli_list_rules(capsys):
    assert cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule_id in analysis.all_rules():
        assert rule_id in out


def test_cli_write_baseline(tmp_path, capsys):
    # a hygiene-free target: those findings are never grandfatherable
    target = str(FIXTURES / "broad_retry.py")
    path = tmp_path / "bl.json"
    assert cli.main([target, "--baseline", str(path),
                     "--write-baseline"]) == 0
    assert cli.main([target, "--baseline", str(path)]) == 0


def test_cli_write_baseline_is_idempotent(tmp_path, capsys):
    """Regenerating must not absorb its own entries: a second
    --write-baseline run writes the SAME file, and the tree still
    passes against it (the write path ignores the loaded baseline)."""
    target = str(FIXTURES / "broad_retry.py")
    path = tmp_path / "bl.json"
    assert cli.main([target, "--baseline", str(path),
                     "--write-baseline"]) == 0
    first = path.read_text()
    assert json.loads(first)["entries"]
    assert cli.main([target, "--baseline", str(path),
                     "--write-baseline"]) == 0
    assert path.read_text() == first
    assert cli.main([target, "--baseline", str(path)]) == 0


def test_cli_write_baseline_excludes_hygiene_findings(tmp_path, capsys):
    """--write-baseline must not grandfather suppression-hygiene: an
    unjustified directive stays a failure even after regenerating."""
    path = tmp_path / "bl.json"
    assert cli.main([str(FIXTURES / "suppression_no_reason.py"),
                     "--baseline", str(path), "--write-baseline"]) == 0
    entries = json.loads(path.read_text())["entries"]
    assert all(e["rule"] != "suppression-hygiene" for e in entries)
    assert cli.main([str(FIXTURES / "suppression_no_reason.py"),
                     "--baseline", str(path)]) == 1


# ---------------------------------------------------------------------------
# Concurrency-rule self-tests: seed each hazard through the framework
# (the acceptance-criteria quartet, plus resolution edge cases)
# ---------------------------------------------------------------------------


def test_lock_order_cycle_is_caught():
    source = (FIXTURES / "lock_order_cycle.py").read_text()
    res = _run(source, rule_ids=["lock-order"])
    assert len(res.findings) == 1
    msg = res.findings[0].message
    assert "cycle" in msg and "TwoLocks._a" in msg and "TwoLocks._b" in msg


def test_lock_order_flags_plain_lock_reacquired_through_helper():
    """Interprocedural self-deadlock: a method holding a plain Lock
    calls a helper that takes the same Lock again."""
    source = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "    def outer(self):\n"
        "        with self._lock:\n"
        "            self._helper()\n"
        "    def _helper(self):\n"
        "        with self._lock:\n"
        "            self._n += 1\n"
    )
    res = _run(source, rule_ids=["lock-order"])
    assert len(res.findings) == 1
    assert "re-acquired" in res.findings[0].message


def test_lock_order_rlock_reacquisition_is_fine():
    source = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.RLock()\n"
        "    def outer(self):\n"
        "        with self._lock:\n"
        "            with self._lock:\n"
        "                pass\n"
    )
    assert not _run(source, rule_ids=["lock-order"]).findings


def test_lock_order_nonblocking_acquire_is_not_an_edge():
    """acquire(blocking=False) cannot deadlock — the executor's stale
    sweep relies on exactly this exemption."""
    source = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._a = threading.Lock()\n"
        "        self._b = threading.Lock()\n"
        "    def forward(self):\n"
        "        with self._a:\n"
        "            with self._b:\n"
        "                pass\n"
        "    def sweep(self):\n"
        "        with self._b:\n"
        "            if self._a.acquire(blocking=False):\n"
        "                self._a.release()\n"
    )
    assert not _run(source, rule_ids=["lock-order"]).findings


def test_wait_holding_foreign_lock_is_caught():
    source = (FIXTURES / "wait_foreign_lock.py").read_text()
    res = _run(source, rule_ids=["wait-holding-lock"])
    assert len(res.findings) == 1
    assert "Waiter._lock" in res.findings[0].message


def test_wait_under_own_lock_only_is_fine():
    source = (
        "import threading\n"
        "class W:\n"
        "    def __init__(self):\n"
        "        self._cond = threading.Condition()\n"
        "        self.ready = False\n"
        "    def block(self):\n"
        "        with self._cond:\n"
        "            while not self.ready:\n"
        "                self._cond.wait()\n"
    )
    assert not _run(source, rule_ids=["wait-holding-lock"]).findings


def test_blocking_under_lock_is_caught_directly():
    res = _run((FIXTURES / "blocking_under_lock.py").read_text(),
               rule_ids=["blocking-under-lock"])
    assert len(res.findings) == 1
    assert "time.sleep" in res.findings[0].message


def test_blocking_under_lock_propagates_through_helper_calls():
    """The exporter shape: the lock is taken in one method, the file
    write lives in a helper — the finding lands on the write."""
    source = (
        "import threading\n"
        "class E:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def tick(self):\n"
        "        with self._lock:\n"
        "            self._flush()\n"
        "    def _flush(self):\n"
        "        with open('/tmp/x', 'w') as f:\n"
        "            f.write('snapshot')\n"
    )
    res = _run(source, rule_ids=["blocking-under-lock"])
    lines = sorted(f.line for f in res.findings)
    assert lines == [9, 10]  # open() and .write(), not the call site
    assert all("E._lock" in f.message for f in res.findings)


def test_unguarded_shared_write_is_caught_and_init_exempt():
    res = _run((FIXTURES / "unguarded_write.py").read_text(),
               rule_ids=["unguarded-shared-write"])
    assert len(res.findings) == 1
    assert "RacyCounter.bump" in res.findings[0].message
    # __init__'s writes and the guarded read stayed clean: only line 12
    assert res.findings[0].line == 12


def test_guarded_write_and_lockless_class_are_fine():
    source = (
        "import threading\n"
        "class Guarded:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self._n += 1\n"
        "class NoLocks:\n"
        "    def set(self, v):\n"
        "        self._v = v\n"  # no lock owned: out of scope
    )
    assert not _run(source, rule_ids=["unguarded-shared-write"]).findings


def test_thread_lifecycle_catches_unnamed_and_unjoinable():
    res = _run((FIXTURES / "thread_lifecycle.py").read_text(),
               rule_ids=["thread-lifecycle"])
    msgs = " | ".join(f.message for f in res.findings)
    assert "without name=" in msgs
    assert "join" in msgs


def test_thread_lifecycle_named_and_joined_is_fine():
    source = (
        "import threading\n"
        "class P:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self.run,\n"
        "                                   name='sparkdl-worker')\n"
        "    def close(self):\n"
        "        self._t.join()\n"
    )
    assert not _run(source, rule_ids=["thread-lifecycle"]).findings


def test_process_lifecycle_catches_unnamed_and_unreapable():
    """The multiprocessing extension (ISSUE 9): an unnamed, non-daemon
    Process in a join-free module is flagged on both counts."""
    res = _run((FIXTURES / "process_lifecycle.py").read_text(),
               rule_ids=["thread-lifecycle"])
    msgs = " | ".join(f.message for f in res.findings)
    assert "multiprocessing.Process" in msgs
    assert "without name=" in msgs
    assert "join" in msgs


def test_process_lifecycle_named_daemon_via_get_context_is_fine():
    """The decode pool's exact shape: a module-level get_context(...)
    variable's .Process(...) with name= and daemon=True, joined in
    close() — clean on every count."""
    source = (
        "import multiprocessing\n"
        "_CTX = multiprocessing.get_context('spawn')\n"
        "class Pool:\n"
        "    def spawn(self, i):\n"
        "        p = _CTX.Process(target=print, name=f'sparkdl-{i}',\n"
        "                         daemon=True)\n"
        "        p.start()\n"
        "        return p\n"
        "    def close(self, p):\n"
        "        p.join()\n"
    )
    assert not _run(source, rule_ids=["thread-lifecycle"]).findings


def test_process_lifecycle_daemon_without_join_is_fine():
    """daemon=True satisfies the reap requirement on its own (the
    interpreter kills daemonic workers at exit); name= is still
    required."""
    source = (
        "import multiprocessing as mp\n"
        "def launch(fn):\n"
        "    p = mp.Process(target=fn, name='sparkdl-w', daemon=True)\n"
        "    p.start()\n"
        "    return p\n"
    )
    assert not _run(source, rule_ids=["thread-lifecycle"]).findings


def test_process_lifecycle_local_get_context_resolves():
    """A get_context(...) bound to a LOCAL inside the function is a
    process factory too."""
    source = (
        "import multiprocessing\n"
        "def launch(fn):\n"
        "    ctx = multiprocessing.get_context('spawn')\n"
        "    p = ctx.Process(target=fn)\n"
        "    p.start()\n"
        "    return p\n"
    )
    res = _run(source, rule_ids=["thread-lifecycle"])
    msgs = " | ".join(f.message for f in res.findings)
    assert "multiprocessing.Process" in msgs and "without name=" in msgs


def test_process_handle_lookup_is_not_a_process_factory():
    """psutil-style `X.Process(pid)` HANDLE lookups on arbitrary
    receivers create nothing and must not be flagged."""
    source = (
        "import psutil\n"
        "def rss(pid):\n"
        "    return psutil.Process(pid).memory_info().rss\n"
    )
    assert not _run(source, rule_ids=["thread-lifecycle"]).findings


def test_same_class_name_in_two_modules_is_not_a_phantom_cycle():
    """Lock identities are module-qualified: two unrelated `Worker`
    classes nesting their locks in opposite orders are four distinct
    locks, not a deadlock."""
    a = (
        "import threading\n"
        "class Worker:\n"
        "    def __init__(self):\n"
        "        self._x = threading.Lock()\n"
        "        self._y = threading.Lock()\n"
        "    def go(self):\n"
        "        with self._x:\n"
        "            with self._y:\n"
        "                pass\n"
    )
    b = a.replace("with self._x:", "with self._TMP:") \
         .replace("with self._y:", "with self._x:") \
         .replace("with self._TMP:", "with self._y:")
    res = analysis.analyze_sources(
        [framework.SourceFile.from_source(a, rel="mod_a.py"),
         framework.SourceFile.from_source(b, rel="mod_b.py")],
        rule_ids=["lock-order"])
    assert not res.findings


def test_thread_lifecycle_sees_module_level_threads():
    """An import-time `threading.Thread(...)` (the shape most likely to
    leak) is not invisible just because it lives outside any def."""
    source = (
        "import threading\n"
        "_t = threading.Thread(target=print)\n"
        "_t.start()\n"
    )
    res = _run(source, rule_ids=["thread-lifecycle"])
    msgs = " | ".join(f.message for f in res.findings)
    assert "without name=" in msgs and "join" in msgs


def test_str_join_is_not_a_thread_join():
    """`sep.join(items)` on a non-literal receiver is str.join: neither
    a blocking call under a lock nor a module join path."""
    source = (
        "import threading\n"
        "_lock = threading.Lock()\n"
        "def fmt(sep, items):\n"
        "    with _lock:\n"
        "        return sep.join(items)\n"
        "def leak(fn):\n"
        "    threading.Thread(target=fn, name='sparkdl-x').start()\n"
    )
    res = _run(source, rule_ids=["blocking-under-lock",
                                 "thread-lifecycle"])
    # no blocking finding for str.join; the named thread still lacks a
    # REAL join path (sep.join must not satisfy it)
    assert [f.rule for f in res.findings] == ["thread-lifecycle"]
    assert "join" in res.findings[0].message


def test_blank_line_between_directive_and_statement_still_suppresses():
    source = (
        "import threading\n"
        "import time\n"
        "_lock = threading.Lock()\n"
        "def t():\n"
        "    with _lock:\n"
        "        # sparkdl: allow(blocking-under-lock): spaced out\n"
        "\n"
        "        time.sleep(0.1)\n"
    )
    res = _run(source)
    assert not res.findings
    assert len(res.suppressed) == 1


def test_blocking_reachability_survives_call_cycles():
    """Mutually-recursive helpers: the blocking site must still be
    reachable from a locked caller regardless of traversal order (the
    closure is a fixpoint, not a memoized DFS that caches partial
    results for cycle participants)."""
    source = (
        "import threading\n"
        "import time\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def a(self, n):\n"
        "        time.sleep(0.1)\n"
        "        if n:\n"
        "            self.b(n - 1)\n"
        "    def b(self, n):\n"
        "        if n:\n"
        "            self.a(n - 1)\n"
        "    def locked_entry(self):\n"
        "        with self._lock:\n"
        "            self.b(3)\n"
    )
    res = _run(source, rule_ids=["blocking-under-lock"])
    assert len(res.findings) == 1
    assert res.findings[0].line == 7  # the sleep, via b -> a


def test_annotated_param_lock_resolution():
    """The executor idiom: a method of one class locks another class's
    condition through an annotated parameter."""
    source = (
        "import threading\n"
        "import time\n"
        "class State:\n"
        "    def __init__(self):\n"
        "        self.cond = threading.Condition()\n"
        "class Service:\n"
        "    def drain(self, state: State):\n"
        "        with state.cond:\n"
        "            time.sleep(0.5)\n"
    )
    res = _run(source, rule_ids=["blocking-under-lock"])
    assert len(res.findings) == 1
    assert "State.cond" in res.findings[0].message
