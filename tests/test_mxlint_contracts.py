"""ISSUE-15 contract-soundness passes: fault-site-soundness,
deadline-soundness, telemetry-drift — pos/neg/suppression fixtures,
witness chains, registry round-trips, doc-regen check, the
repo-tree-clean gate, and the --changed acceptance (a reintroduced
typo'd fault site and an undeadlined sleep fire through unchanged
helpers).

Pure-AST plus one imported-registry round trip: no jax, milliseconds
(tier-1 budget discipline — the file name sorts into the executed
window).
"""
import json
import logging
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.mxlint import PASSES, Project, lint_paths, lint_sources  # noqa: E402
from tools.mxlint.passes.fault_site import globs_intersect          # noqa: E402

SITES = {"serving.execute": None,
         "decode.step": ("fail", "delay", "corrupt", "stall"),
         "kv_cache.allocate": ("fail",),
         "replica.<rid>.heartbeat": None,
         "replica.<rid>.decode.step": None}


def run(src, path="mxnet_tpu/serving/fixture.py", select=None,
        sites=SITES, **proj):
    proj.setdefault("fault_sites", sites)
    proj.setdefault("ci_shell_texts", {})
    return lint_sources({path: textwrap.dedent(src)}, select=select,
                        project=Project(**proj))


def ids(issues):
    return [i.pass_id for i in issues]


# ========================================================= glob matching
def test_glob_intersection():
    assert globs_intersect("serving.*", "serving.execute")
    assert globs_intersect("replica.r1.*", "replica.*.decode.step")
    assert globs_intersect("*", "anything.at.all")
    assert globs_intersect("a.b", "a.b")
    assert not globs_intersect("serving.exeucte", "serving.execute")
    assert not globs_intersect("train.*", "serving.execute")
    assert globs_intersect("a.?", "a.b")
    assert not globs_intersect("a.?", "a.bc")


# ==================================================== fault-site-soundness
def test_fault_site_fires_on_typo_literal():
    issues = run("""
        from mxnet_tpu import faults as _faults
        def f():
            _faults.inject("serving.exeucte")
    """, select=["fault-site-soundness"])
    assert ids(issues) == ["fault-site-soundness"]
    assert "serving.exeucte" in issues[0].message
    assert "can never fire" in issues[0].message


def test_fault_site_quiet_on_declared_and_template():
    issues = run("""
        from mxnet_tpu import faults as _faults
        def f(rid):
            _faults.inject("serving.execute")
            _faults.check("kv_cache.allocate")
            _faults.inject(f"replica.{rid}.heartbeat")
    """, select=["fault-site-soundness"])
    assert issues == []


def test_fault_site_dynamic_scope_concat():
    issues = run("""
        from mxnet_tpu import faults as _faults
        class Engine:
            def go(self):
                _faults.inject(self.fault_scope + ".step")
                _faults.inject(self.fault_scope + ".stepp")
    """, select=["fault-site-soundness"])
    assert ids(issues) == ["fault-site-soundness"]
    assert "*.stepp" in issues[0].message


def test_fault_site_helper_routed_with_witness():
    issues = run("""
        from mxnet_tpu import faults as _faults
        def _inject(site, modes):
            raise _faults.InjectedFault(site)
        def wrapper(site):
            _inject(site, modes=("fail",))
        def g():
            wrapper("checkpoint.sav")
    """, select=["fault-site-soundness"])
    assert ids(issues) == ["fault-site-soundness"]
    assert "checkpoint.sav" in issues[0].message
    assert "via wrapper" in issues[0].message
    assert issues[0].line == 8      # at the literal's call site


def test_fault_site_spec_pattern_matches_nothing():
    issues = run("""
        from mxnet_tpu import faults
        def t(monkeypatch):
            with faults.plan("servig.*=fail"):
                pass
            monkeypatch.setenv("MXNET_FAULTS", "decode.step=fail")
            monkeypatch.setenv("MXNET_FAULTS", "decode.stepp=fail")
    """, select=["fault-site-soundness"])
    assert ids(issues) == ["fault-site-soundness"] * 2
    assert "servig.*" in issues[0].message
    assert "decode.stepp" in issues[1].message


def test_fault_site_spec_dead_mode():
    issues = run("""
        from mxnet_tpu import faults
        def t():
            faults.install("kv_cache.allocate=corrupt")
            faults.install("kv_cache.allocate=fail")
    """, select=["fault-site-soundness"])
    assert ids(issues) == ["fault-site-soundness"]
    assert "honors mode" in issues[0].message


def test_fault_site_fstring_spec_glob_ok():
    issues = run("""
        from mxnet_tpu import faults
        def t(victim):
            with faults.plan(f"replica.{victim}.heartbeat=stall"):
                pass
    """, select=["fault-site-soundness"])
    assert issues == []


def test_fault_site_suppression_honored():
    issues = run("""
        from mxnet_tpu import faults as _faults
        def f():
            _faults.inject("x.y")  # mxlint: disable=fault-site-soundness
    """, select=["fault-site-soundness"])
    assert issues == []


def test_fault_site_env_assignment_checked():
    issues = run("""
        import os
        def t():
            os.environ["MXNET_FAULTS"] = "no.such.site=fail"
    """, select=["fault-site-soundness"])
    assert ids(issues) == ["fault-site-soundness"]


def test_fault_site_ci_shell_specs_checked():
    issues = run("""
        def nothing():
            pass
    """, select=["fault-site-soundness"],
        ci_shell_texts={"ci/job.sh": "export MXNET_FAULTS='oops.x=fail'\n"})
    assert ids(issues) == ["fault-site-soundness"]
    assert issues[0].path == "ci/job.sh" and issues[0].line == 1


def test_fault_site_ci_shell_dead_mode_checked():
    """Review fix: the ci/*.sh scan validates modes like the Python
    spec check — `kv_cache.allocate=corrupt` can never fire."""
    issues = run("""
        def nothing():
            pass
    """, select=["fault-site-soundness"],
        ci_shell_texts={
            "ci/job.sh": "MXNET_FAULTS='kv_cache.allocate=corrupt'\n",
            "ci/ok.sh": "MXNET_FAULTS='kv_cache.allocate=fail'\n"})
    assert ids(issues) == ["fault-site-soundness"]
    assert issues[0].path == "ci/job.sh"
    assert "honors mode" in issues[0].message


def test_fault_site_ci_shell_quoted_spec_with_spaces():
    """Review fix: a quoted multi-rule spec may carry whitespace
    between clauses (legal at runtime — FaultPlan.parse strips each
    clause), so the scan must read to the closing quote, not the
    first space — otherwise the typo'd second clause escapes."""
    issues = run("""
        def nothing():
            pass
    """, select=["fault-site-soundness"],
        ci_shell_texts={"ci/job.sh": 'export MXNET_FAULTS='
                        '"serving.execute=fail; decode.stepp=stall"\n'})
    assert ids(issues) == ["fault-site-soundness"]
    assert "decode.stepp" in issues[0].message


def test_fault_site_template_literal_pattern_is_dead():
    """Review fix: a spec pattern that copy-pastes a template name
    from the docs ('replica.<rid>.heartbeat') is dead — '<rid>' is
    literal to fnmatch, so glob intersection against the template must
    not wave it through.  The glob spelling is the live form."""
    issues = run("""
        import os
        def f():
            os.environ["MXNET_FAULTS"] = "replica.<rid>.heartbeat=stall"
    """, select=["fault-site-soundness"])
    assert ids(issues) == ["fault-site-soundness"]
    issues = run("""
        import os
        def f():
            os.environ["MXNET_FAULTS"] = "replica.*.heartbeat=stall"
    """, select=["fault-site-soundness"])
    assert issues == []


def test_fault_site_harvests_declarations_from_scanned_files():
    # a file declaring its own site makes that site valid project-wide
    issues = lint_sources({
        "mxnet_tpu/plugin.py": textwrap.dedent("""
            from mxnet_tpu.faults import declare_fault_site
            declare_fault_site("plugin.flush", modes=("fail",))
        """),
        "mxnet_tpu/user.py": textwrap.dedent("""
            from mxnet_tpu import faults as _faults
            def f():
                _faults.inject("plugin.flush")
        """)}, select=["fault-site-soundness"],
        project=Project(ci_shell_texts={}))
    assert issues == []


def test_fault_site_repo_registry_fallback():
    """Linting a tests/-style file with NO declare_fault_site in the
    scanned set falls back to parsing the repo's faults.py — the CI
    run over tests/ and benchmark/ validates against the real
    catalogue."""
    issues = lint_sources({"tests/t.py": textwrap.dedent("""
        from mxnet_tpu import faults
        def t():
            with faults.plan("serving.execute=fail,times=1"):
                pass
            with faults.plan("serving.exeucte=fail"):
                pass
    """)}, select=["fault-site-soundness"],
        project=Project(ci_shell_texts={}))
    assert ids(issues) == ["fault-site-soundness"]
    assert "serving.exeucte" in issues[0].message


# ============================================= fault registry (runtime)
def test_registry_round_trip_and_parse_warning(caplog):
    from mxnet_tpu import faults
    sites = faults.declared_sites()
    # the catalogue covers every in-tree injection family
    for must in ("serving.execute", "serving.compile", "deploy.execute",
                 "compile_cache.load", "repository.load_artifact",
                 "decode.prefill", "decode.step", "decode.verify",
                 "decode.prefix_lookup", "kv_cache.allocate",
                 "replica.<rid>.execute", "replica.<rid>.heartbeat",
                 "train.step", "train.data.next", "kvstore.push",
                 "kvstore.pull", "kvstore.pushpull", "checkpoint.save",
                 "checkpoint.restore"):
        assert must in sites, must
    assert sites["kv_cache.allocate"].modes == ("fail",)
    assert faults.pattern_matches_declared("replica.r7.decode.step")
    assert not faults.pattern_matches_declared("replica.r7.decode.stepp")
    # review fix: a copy-pasted TEMPLATE name is dead — the literal
    # "<rid>" never fnmatches a runtime site, and glob intersection
    # against the template must not wave it through
    assert not faults.pattern_matches_declared("replica.<rid>.heartbeat")
    assert faults.pattern_matches_declared("replica.*.heartbeat")
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu"):
        # mxlint: disable=fault-site-soundness (deliberately dead
        # pattern: this asserts the runtime warning fires)
        faults.FaultPlan.parse("decode.stepp=fail")
    assert any("can never fire" in r.message for r in caplog.records)
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu"):
        faults.FaultPlan.parse("decode.step=fail,times=1")
    assert not any("can never fire" in r.message
                   for r in caplog.records)


def test_diagnose_reports_mode_dead_rule(capsys):
    """Review fix: diagnose's DEAD RULE report validates the MODE like
    FaultPlan.parse does — kv_cache.allocate is fail-only, so a
    corrupt rule must print as dead, not as a live plan entry."""
    from mxnet_tpu import faults
    import tools.diagnose as dg
    # mxlint: disable=fault-site-soundness (deliberately mode-dead:
    # this asserts the operator-facing DEAD RULE line fires)
    with faults.plan("kv_cache.allocate=corrupt"):
        dg.diagnose()
    out = capsys.readouterr().out
    assert "DEAD RULE" in out and "honors mode" in out


def test_diagnose_has_no_training_performance_section(capsys):
    """``diagnose()`` imports no observatory: it runs to its end and
    prints the sections its docstring lists, in order, and no
    "Training Performance" (the runtime MFU and verdict are gone)."""
    import re
    import tools.diagnose as dg
    dg.diagnose()
    out = capsys.readouterr().out
    assert re.findall(r"^-{10}(.+?)-{10}$", out, re.M) == [
        "Platform Info", "Python Info", "Framework Info", "Environment",
        "Compile Cache", "Concurrency Sanitizer", "Threads",
        "Fault Injection", "Training Resilience", "Replica Serving",
        "Traffic / Autoscaling / Admission",
        "Tracing / Flight Recorder", "Runtime Metrics"]


def test_declare_fault_site_validates():
    import pytest
    from mxnet_tpu import faults
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match="dotted lowercase"):
        faults.declare_fault_site("Bad Site")
    with pytest.raises(MXNetError, match="unknown mode"):
        faults.declare_fault_site("ok.site", modes=("explode",))
    assert "ok.site" not in faults.declared_sites()


# ======================================================= deadline-soundness
def test_deadline_fires_on_sleep_in_entry():
    issues = run("""
        import time
        class ModelServer:
            def predict(self, x):
                time.sleep(0.5)
                return x
    """, select=["deadline-soundness"])
    assert ids(issues) == ["deadline-soundness"]
    assert "ModelServer.predict" in issues[0].message


def test_deadline_fires_through_helpers_with_chain():
    issues = run("""
        import time
        def _pace():
            time.sleep(0.01)
        def helper(x):
            _pace()
            return x
        class ModelServer:
            def _worker_loop(self):
                helper(1)
    """, select=["deadline-soundness"])
    assert ids(issues) == ["deadline-soundness"]
    msg = issues[0].message
    assert "ModelServer._worker_loop" in msg
    assert "via helper" in msg and "_pace" in msg
    assert issues[0].line == 4      # anchored at the sleep


def test_deadline_quiet_when_deadline_consumed():
    issues = run("""
        import time
        class ModelServer:
            def predict(self, x, deadline):
                while not deadline.expired():
                    time.sleep(0.01)
            def generate(self, req):
                req.event.wait(req.deadline.remaining())
            def _worker_loop(self):
                retry_call(lambda: 1, retries=2, backoff_ms=1,
                           deadline=self._dl)
    """, select=["deadline-soundness"])
    assert issues == []


def test_deadline_wait_and_queue_get_sinks():
    issues = run("""
        class DecodeEngine:
            def _loop(self):
                self._cond.wait()
            def step(self):
                self._queue.get()
    """, select=["deadline-soundness"])
    assert ids(issues) == ["deadline-soundness"] * 2
    assert "wait" in issues[0].message
    assert "queue pop" in issues[1].message


def test_deadline_bounded_wait_and_get_quiet():
    issues = run("""
        class DecodeEngine:
            def _loop(self):
                self._cond.wait(0.25)
            def step(self):
                self._queue.get(timeout=1.0)
    """, select=["deadline-soundness"])
    assert issues == []


def test_deadline_retry_call_without_deadline():
    issues = run("""
        from mxnet_tpu.serving.resilience import retry_call
        class ModelServer:
            def predict(self, x):
                return retry_call(lambda: x, retries=3, backoff_ms=5)
    """, select=["deadline-soundness"])
    assert ids(issues) == ["deadline-soundness"]
    assert "retry_call" in issues[0].message


def test_deadline_unreachable_code_is_quiet():
    issues = run("""
        import time
        def offline_tool():
            time.sleep(5)       # not reachable from any entry point
    """, select=["deadline-soundness"])
    assert issues == []


def test_deadline_suppression_carries_contract():
    issues = run("""
        class ModelServer:
            def _worker_loop(self):
                # mxlint: disable=deadline-soundness (contract: idle
                # park; every enqueue notifies)
                self._cond.wait()
    """, select=["deadline-soundness"])
    assert issues == []


# ========================================================= telemetry-drift
DOC_METRICS = {"serving.requests": 10, "serving.ghost.metric": 11}
DOC_SPANS = {"serving.predict": 20, "fault.fail": 21, "fault.stall": 22,
             "decode.ghost": 23}


def test_telemetry_undocumented_metric_and_span():
    issues = run("""
        from mxnet_tpu import tracing as _tr
        from mxnet_tpu.runtime_metrics import counter
        REQS = counter("serving.requests", "ok")
        NEW = counter("serving.brand.new", "undocumented")
        def f():
            with _tr.span("serving.predict"):
                pass
            with _tr.span("serving.mystery"):
                pass
    """, path="mxnet_tpu/runtime_metrics.py",
        select=["telemetry-drift"],
        doc_metrics=DOC_METRICS, doc_spans=DOC_SPANS)
    msgs = [i.message for i in issues]
    assert any("serving.brand.new" in m and "undocumented" in m
               for m in msgs)
    assert any("serving.mystery" in m for m in msgs)
    assert not any("serving.requests'" in m and "undocumented" in m
                   for m in msgs)


def test_telemetry_documented_but_dead_rows():
    issues = run("""
        from mxnet_tpu import tracing as _tr
        from mxnet_tpu.runtime_metrics import counter
        REQS = counter("serving.requests", "ok")
        def f():
            with _tr.trace("serving.predict"):
                pass
    """, path="mxnet_tpu/runtime_metrics.py",
        select=["telemetry-drift"],
        doc_metrics=DOC_METRICS, doc_spans=DOC_SPANS)
    # spans authority (tracing.py) not scanned -> span dead rows quiet;
    # metrics authority scanned -> the ghost metric row flags at its
    # doc line
    dead = [i for i in issues if "emitted nowhere" in i.message]
    assert len(dead) == 1
    assert "serving.ghost.metric" in dead[0].message
    assert dead[0].path.endswith("observability.md")
    assert dead[0].line == 11


def test_telemetry_span_glob_covers_documented_family():
    issues = run("""
        from mxnet_tpu import tracing as _tr
        def observe(mode, ctx, now):
            _tr.record_span(f"fault.{mode}", ctx, now, now)
    """, path="mxnet_tpu/tracing.py", select=["telemetry-drift"],
        doc_metrics={}, doc_spans={"fault.fail": 21, "fault.stall": 22})
    assert issues == []     # the glob covers both documented rows


def test_telemetry_span_glob_matching_nothing_flags():
    issues = run("""
        from mxnet_tpu import tracing as _tr
        def observe(kind, ctx, now):
            _tr.record_span(f"ghost.{kind}", ctx, now, now)
    """, path="mxnet_tpu/tracing.py", select=["telemetry-drift"],
        doc_metrics={}, doc_spans={"fault.fail": 21})
    msgs = [i.message for i in issues]
    assert any("ghost.*" in m for m in msgs)


def test_telemetry_suppression_honored():
    issues = run("""
        from mxnet_tpu.runtime_metrics import counter
        X = counter("sneaky.metric", "x")  # mxlint: disable=telemetry-drift
    """, path="mxnet_tpu/runtime_metrics.py",
        select=["telemetry-drift"], doc_metrics={"a.b": 1}, doc_spans={})
    assert [i for i in issues if i.path.endswith("fixture.py")
            or "sneaky" in i.message] == []


def test_telemetry_partial_injection_falls_back_per_side():
    """Review fix: Project(doc_metrics=...) with doc_spans left None
    parses the repo doc for the SPANS side (the core.Project per-side
    fallback contract) instead of treating every span as undocumented.
    `serving.batch` is documented in the real docs/observability.md."""
    issues = run("""
        from mxnet_tpu import tracing as _tr
        def f():
            with _tr.span("serving.batch"):
                pass
    """, select=["telemetry-drift"], doc_metrics={"x.y": 1})
    assert issues == [], "\n".join(str(i) for i in issues)


def test_telemetry_partial_run_never_reports_dead_rows():
    # no authority module in the scanned set -> both dead-row
    # directions stay quiet even though nothing is emitted
    issues = run("""
        def f():
            pass
    """, select=["telemetry-drift"],
        doc_metrics=DOC_METRICS, doc_spans=DOC_SPANS)
    assert issues == []


def test_telemetry_doc_parser_reads_repo_doc():
    from tools.mxlint.passes.telemetry_drift import _doc_tables
    with open(os.path.join(REPO, "docs", "observability.md")) as fh:
        metrics, spans, relative = _doc_tables(fh.read())
    assert "serving.requests" in metrics
    assert "kvstore.push.bytes" in metrics      # normalized, not '.push.bytes'
    assert "serving.predict" in spans and "decode.request" in spans
    assert relative == []       # relative tokens are themselves findings


# ===================================================== repo acceptance gates
def test_repo_tree_clean_under_contract_passes():
    """ISSUE-15 acceptance: the three new passes are clean over
    mxnet_tpu/ + tools/ (sweep findings fixed or contract-noted)."""
    issues = lint_paths(
        [os.path.join(REPO, "mxnet_tpu"), os.path.join(REPO, "tools")],
        select=["fault-site-soundness", "deadline-soundness",
                "telemetry-drift"])
    assert issues == [], "\n".join(str(i) for i in issues)


def test_tests_and_benchmarks_fault_specs_clean():
    """The CI line: chaos specs in tests/ and benchmark/ validate
    against the registry (synthetic machinery sites carry their
    file-level suppression)."""
    issues = lint_paths(
        [os.path.join(REPO, "tests"), os.path.join(REPO, "benchmark")],
        select=["fault-site-soundness"])
    assert issues == [], "\n".join(str(i) for i in issues)


def test_pass_catalogue_is_16():
    assert len(PASSES) == 22


def test_fault_doc_tables_fresh():
    """Doc-regen gate (same discipline as env_vars.md): the generated
    fault-site tables match the committed docs."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools",
                                      "gen_fault_docs.py"), "--check"],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr


def test_fault_doc_missing_end_marker_is_diagnosed(tmp_path, monkeypatch):
    """Review fix: a doc edit that drops the END marker while keeping
    BEGIN gets the same clean 'missing marker' diagnostic as a missing
    BEGIN — not an unpacking traceback."""
    import tools.gen_fault_docs as gfd
    doc = tmp_path / "serving.md"
    doc.write_text("intro\n" + gfd.BEGIN + "\n| old |\n")   # no END
    monkeypatch.setattr(gfd, "DOCS", {"serving": str(doc)})
    assert gfd.main(check=True) == 2


# ============================================== --changed acceptance (git)
def _git(cwd, *argv):
    proc = subprocess.run(
        ["git"] + list(argv), cwd=cwd, capture_output=True, text=True,
        env=dict(os.environ, GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
                 GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t",
                 HOME=str(cwd)))
    assert proc.returncode == 0, proc.stderr
    return proc.stdout


def mxlint(*argv, cwd=REPO):
    env = dict(os.environ, PYTHONPATH=REPO)
    return subprocess.run(
        [sys.executable, "-m", "tools.mxlint"] + list(argv),
        cwd=cwd, capture_output=True, text=True, env=env)


HELPER = """\
import time

def pace(ms):
    time.sleep(ms / 1e3)

def fire(faults, site):
    faults.inject(site)
"""

CALLER_V1 = """\
def untouched():
    pass
"""

CALLER_V2 = """\
from .helper import fire, pace
from mxnet_tpu import faults as _faults

class ModelServer:
    def predict(self, x):
        pace(5)                         # undeadlined sleep, 1 hop down
        fire(_faults, "decode.prefil")  # typo'd site through a helper
        return x
"""


def test_changed_mode_catches_reintroduced_contract_bugs(tmp_path):
    """The ISSUE-15 acceptance: a reintroduced typo'd fault site AND an
    undeadlined time.sleep on the predict path are caught by full lint
    AND by --changed when only the caller changed — the interprocedural
    findings fire through the unchanged helper."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "helper.py").write_text(HELPER)
    (pkg / "caller.py").write_text(CALLER_V1)
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", ".")
    _git(tmp_path, "commit", "-qm", "seed")
    sel = "fault-site-soundness,deadline-soundness"
    proc = mxlint("pkg", "--select", sel, cwd=tmp_path)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # reintroduce both bug shapes in caller.py only
    (pkg / "caller.py").write_text(CALLER_V2)
    full = mxlint("pkg", "--select", sel, "--format", "json",
                  cwd=tmp_path)
    assert full.returncode == 1, full.stderr
    findings = [json.loads(l) for l in full.stdout.splitlines()]
    by_pass = {f["pass"] for f in findings}
    assert by_pass == {"fault-site-soundness", "deadline-soundness"}
    fault = next(f for f in findings
                 if f["pass"] == "fault-site-soundness")
    assert "decode.prefil" in fault["message"]
    assert "via fire" in fault["message"]
    assert fault["file"] == os.path.join("pkg", "caller.py")
    dl = next(f for f in findings if f["pass"] == "deadline-soundness")
    assert "ModelServer.predict" in dl["message"]
    assert "via pace" in dl["message"]
    # the sleep anchors in the UNCHANGED helper: --changed must still
    # surface the typo'd-site finding at the changed call site, and
    # the full run remains the net for helper-anchored findings
    changed = mxlint("pkg", "--select", sel, "--format", "json",
                     "--changed", cwd=tmp_path)
    assert changed.returncode == 1, changed.stderr
    cfind = [json.loads(l) for l in changed.stdout.splitlines()]
    assert all(f["file"] == os.path.join("pkg", "caller.py")
               for f in cfind)
    assert any(f["pass"] == "fault-site-soundness"
               and "decode.prefil" in f["message"] for f in cfind)
