"""tools/fmlint whole-program layer: the project loader (imports, call
graph, summaries), the cross-file rules R007-R012, the committed
baseline, --json — and the seeded-mutant acceptance test proving R007
catches a rank-gated collective planted in the REAL checkpoint.py
restore path."""

import json
import os
import textwrap

import pytest

from tools.fmlint.core import (apply_baseline, main, run_paths,
                               write_baseline)
from tools.fmlint.project import load_project, parse_files

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _project(tmp_path, files):
    """Write {relpath: source} under tmp_path, return (root, paths)."""
    paths = []
    for rel, body in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(body))
        if rel.endswith(".py"):
            paths.append(str(p))
    return str(tmp_path), paths


def _load(tmp_path, files):
    _, paths = _project(tmp_path, files)
    return load_project(parse_files(paths))


def _findings(tmp_path, files, rule=None):
    root, _ = _project(tmp_path, files)
    # Lint the directory (not the file list): directory linting is the
    # shape the repo gate uses.
    found = run_paths([root])
    if rule is not None:
        found = [f for f in found if f.rule == rule]
    return found


# --- project loader -------------------------------------------------------

def test_import_and_call_graph_resolution(tmp_path):
    proj = _load(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/a.py": """\
            from pkg.b import helper
            import pkg.b as bee
            def top():
                helper()
                bee.other()
        """,
        "pkg/b.py": """\
            def helper():
                pass
            def other():
                pass
        """,
    })
    fn = proj.functions["pkg.a.top"]
    assert fn.calls == {"pkg.b.helper", "pkg.b.other"}


def test_call_on_a_parameter_annotated_with_a_class_resolves(tmp_path):
    """``loop.step()`` reaches ``Loop.step`` when the parameter says
    what it is (train.py's loops drive ``StepLoop`` this way, and the
    protocol checker has to see the step's collectives through the
    call); an unannotated or foreign-typed receiver stays unresolved."""
    proj = _load(tmp_path, {
        "pkg/__init__.py": "",
        "pkg/a.py": """\
            class Loop:
                def step(self):
                    guarded_collective(f, label="x")
            def drive(loop: Loop, other, named: "Loop", n: int):
                loop.step()
                other.step()
                named.step()
                n.step()
                def inner():
                    loop.step()
        """})
    assert proj.functions["pkg.a.drive"].calls == {"pkg.a.Loop.step"}
    assert proj.functions["pkg.a.drive.inner"].calls == {
        "pkg.a.Loop.step"}
    assert "guarded_collective" in proj.may_collectives["pkg.a.drive"]


def test_collective_summary_is_transitive(tmp_path):
    proj = _load(tmp_path, {
        "m.py": """\
            from jax.experimental import multihost_utils
            def leaf(x):
                return multihost_utils.process_allgather(x)
            def mid(x):
                return leaf(x)
            def top(x):
                return mid(x)
        """,
    })
    assert proj.collectives_of("m.top") == {"process_allgather"}


def test_thread_summary_reaches_nested_target_and_callees(tmp_path):
    """The Watchdog pattern: the Thread target is a closure defined
    under an ``if``, and it calls a method of the same class."""
    proj = _load(tmp_path, {
        "w.py": """\
            import threading
            class W:
                def check(self):
                    self.count = 1
                def start(self):
                    if True:
                        def loop():
                            self.check()
                        threading.Thread(target=loop).start()
        """,
    })
    assert "w.W.start.loop" in proj.thread_funcs
    assert "w.W.check" in proj.thread_funcs


def test_shared_write_lock_detection(tmp_path):
    proj = _load(tmp_path, {
        "s.py": """\
            class S:
                def locked(self):
                    with self._lock:
                        self.x = 1
                def bare(self):
                    self.y = 2
                    self.items.append(3)
        """,
    })
    locked = proj.functions["s.S.locked"].shared_writes
    bare = proj.functions["s.S.bare"].shared_writes
    assert [w.locked for w in locked] == [True]
    assert [(w.target, w.locked) for w in bare] == [
        ("self.y", False), ("self.items", False)]


def test_shared_write_lock_detected_through_nested_with(tmp_path):
    """A lock `with` nested directly inside another `with` body (the
    open-then-lock shape) must still raise the lock depth."""
    proj = _load(tmp_path, {
        "s.py": """\
            class S:
                def work(self, f):
                    with open(f) as fh:
                        with self._lock:
                            self.n = fh.read()
        """,
    })
    writes = proj.functions["s.S.work"].shared_writes
    assert [(w.target, w.locked) for w in writes] == [("self.n", True)]


def test_shared_write_requires_store_context(tmp_path):
    """Reads inside assignment targets are not writes: `buf[self.idx]`
    READS self.idx, and in a chained store only the outermost
    attribute is written."""
    proj = _load(tmp_path, {
        "s.py": """\
            class S:
                def work(self, buf):
                    buf[self.idx] = 1
                def chain(self):
                    self.a.b = 1
        """,
    })
    assert proj.functions["s.S.work"].shared_writes == []
    assert [w.target
            for w in proj.functions["s.S.chain"].shared_writes] == [
        "self.a.b"]


def test_relative_import_resolution_from_package_init(tmp_path):
    """`from .b import helper` inside pkg/__init__.py: the package
    module's modname IS the package, so level=1 must not strip it —
    the call edge (and any collective behind it) would silently
    vanish otherwise."""
    proj = _load(tmp_path, {
        "pkg/__init__.py": """\
            from .b import helper
            def top():
                helper()
        """,
        "pkg/b.py": """\
            from jax.experimental import multihost_utils
            def helper():
                multihost_utils.process_allgather(None)
        """,
    })
    assert proj.functions["pkg.top"].calls == {"pkg.b.helper"}
    assert proj.collectives_of("pkg.top") == {"process_allgather"}


# --- R007: divergent collective -------------------------------------------

_ALLGATHER_DEF = """\
        from jax.experimental import multihost_utils
"""


def test_r007_flags_rank_gated_collective(tmp_path):
    found = _findings(tmp_path, {"m.py": _ALLGATHER_DEF + """\
        import jax
        def sync(x):
            if jax.process_index() == 0:
                return multihost_utils.process_allgather(x)
    """}, rule="R007")
    assert len(found) == 1
    assert "process_allgather" in found[0].message


def test_r007_flags_transitive_collective_through_call_graph(tmp_path):
    found = _findings(tmp_path, {"m.py": _ALLGATHER_DEF + """\
        import jax
        def deep(x):
            return multihost_utils.broadcast_one_to_all(x)
        def mid(x):
            return deep(x)
        def sync(x):
            if jax.process_index() == 0:
                mid(x)
    """}, rule="R007")
    assert len(found) == 1
    assert "broadcast_one_to_all" in found[0].message


def test_r007_flags_early_return_divergence(tmp_path):
    """`if rank != 0: return` then a collective below: only process 0
    posts it — the same deadlock with no explicit else arm."""
    found = _findings(tmp_path, {"m.py": _ALLGATHER_DEF + """\
        import jax
        def sync(x):
            if jax.process_index() != 0:
                return None
            return multihost_utils.process_allgather(x)
    """}, rule="R007")
    assert len(found) == 1


def test_r007_flags_tainted_local_condition(tmp_path):
    found = _findings(tmp_path, {"m.py": _ALLGATHER_DEF + """\
        import jax
        def sync(x):
            proc0 = jax.process_index() == 0
            if proc0:
                multihost_utils.sync_global_devices("tag")
    """}, rule="R007")
    assert len(found) == 1


def test_r007_allows_matched_collectives_on_both_arms(tmp_path):
    found = _findings(tmp_path, {"m.py": _ALLGATHER_DEF + """\
        import jax
        def sync(x):
            if jax.process_index() == 0:
                v = multihost_utils.process_allgather(x)
            else:
                v = multihost_utils.process_allgather(None)
            return v
    """}, rule="R007")
    assert found == []


def test_r007_allows_process_count_branches(tmp_path):
    """process_count is uniform across processes — branching on it is
    the standard single-process fast path, never divergent."""
    found = _findings(tmp_path, {"m.py": _ALLGATHER_DEF + """\
        import jax
        def sync(x):
            if jax.process_count() > 1:
                return multihost_utils.process_allgather(x)
            return x
    """}, rule="R007")
    assert found == []


def test_r007_broadcast_result_is_not_tainted(tmp_path):
    """A value RETURNED by a collective is rank-uniform (that is the
    agreement protocol); branching on it must not be flagged even when
    the pre-broadcast value was rank-dependent."""
    found = _findings(tmp_path, {"m.py": _ALLGATHER_DEF + """\
        import jax
        def pick():
            return 3
        def sync(x):
            cand = pick() if jax.process_index() == 0 else -1
            cand = int(multihost_utils.broadcast_one_to_all(cand))
            if cand < 0:
                return None
            return multihost_utils.process_allgather(x)
    """}, rule="R007")
    assert found == []


def test_r007_respects_pragma(tmp_path):
    found = _findings(tmp_path, {"m.py": _ALLGATHER_DEF + """\
        import jax
        def sync(x):
            # fmlint: disable=R007 -- peers post the matching call in f
            if jax.process_index() == 0:
                return multihost_utils.process_allgather(x)
    """}, rule="R007")
    assert found == []


def test_r007_seeded_mutant_of_real_checkpoint_restore(tmp_path):
    """Acceptance pin: plant the exact historical bug — the restore
    epoch-override broadcast gated on process_index instead of
    process_count — into the REAL checkpoint.py via a source overlay,
    and prove R007 catches it cross-file while the unmutated repo is
    clean (tests/test_fmlint.py pins the clean half)."""
    ckpt = os.path.join(REPO, "fast_tffm_tpu", "checkpoint.py")
    with open(ckpt, encoding="utf-8") as fh:
        src = fh.read()
    needle = "if jax.process_count() > 1:"
    assert src.count(needle) == 1, "mutation site drifted"
    mutated = src.replace(needle, "if jax.process_index() == 0:")
    found = run_paths([os.path.join(REPO, "fast_tffm_tpu")],
                      overlay={ckpt: mutated})
    r007 = [f for f in found if f.rule == "R007"]
    assert len(r007) == 1, "\n".join(f.render() for f in found)
    assert r007[0].path.endswith("checkpoint.py")
    assert "guarded_collective" in r007[0].message
    # The mutation introduced nothing else: every other rule stays
    # clean, so the one finding IS the planted deadlock.
    assert [f.rule for f in found] == ["R007"]


# --- R008: unsynchronized shared mutation ---------------------------------

_THREADED = """\
    import threading
    class C:
        def __init__(self):
            self.n = 0
        def work(self):
            {body}
        def start(self):
            threading.Thread(target=self.work).start()
"""


def _threaded(body):
    return {"m.py": _THREADED.format(body=body)}


def test_r008_flags_unlocked_thread_write(tmp_path):
    found = _findings(tmp_path, _threaded("self.n += 1"), rule="R008")
    assert len(found) == 1
    assert "self.n" in found[0].message


def test_r008_flags_transitive_thread_callee(tmp_path):
    found = _findings(tmp_path, {"m.py": """\
        import threading
        class C:
            def helper(self):
                self.state = "x"
            def work(self):
                self.helper()
            def start(self):
                threading.Thread(target=self.work).start()
    """}, rule="R008")
    assert len(found) == 1
    assert "helper" in found[0].message


def test_r008_allows_lock_held_writes(tmp_path):
    found = _findings(
        tmp_path,
        _threaded("with self._lock:\n                self.n += 1"),
        rule="R008")
    assert found == []


def test_r008_allows_main_thread_only_functions(tmp_path):
    found = _findings(tmp_path, {"m.py": """\
        class C:
            def work(self):
                self.n = 1
    """}, rule="R008")
    assert found == []


def test_r008_init_is_exempt(tmp_path):
    """Construction happens before the thread exists; __init__ writes
    are the setup, not the race."""
    found = _findings(tmp_path, {"m.py": """\
        import threading
        class C:
            def __init__(self):
                self.n = 0
                threading.Thread(target=self.__init__).start()
    """}, rule="R008")
    assert found == []


def test_r008_respects_pragma(tmp_path):
    found = _findings(
        tmp_path,
        _threaded("self.n += 1  # fmlint: disable=R008 -- single writer"),
        rule="R008")
    assert found == []


# --- R009: config/knob drift ----------------------------------------------

_CFG_PY = """\
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class FmConfig:
        factor_num: int = 8
        metrics_file: str = ""

        @property
        def row_dim(self):
            return self.factor_num + 1

    _GENERAL_KEYS = {"factor_num": int}
    _TRAIN_KEYS = {"metrics_file": str}
"""

_SAMPLE_OK = """\
    ; factor_num and metrics_file documented here
    [General]
    factor_num = 8
"""

_README_OK = "factor_num and metrics_file\n"


def _r009_files(cfg=_CFG_PY, sample=_SAMPLE_OK, readme=_README_OK,
                extra=None):
    files = {"fast_tffm_tpu/config.py": cfg, "sample.cfg": sample,
             "README.md": readme}
    files.update(extra or {})
    return files


def test_r009_clean_when_docs_cover_schema(tmp_path):
    assert _findings(tmp_path, _r009_files(), rule="R009") == []


def test_r009_flags_knob_missing_from_sample_cfg(tmp_path):
    found = _findings(tmp_path, _r009_files(
        sample="[General]\nfactor_num = 8\n",
        readme=_README_OK), rule="R009")
    assert len(found) == 1
    assert "metrics_file" in found[0].message
    assert "sample.cfg" in found[0].message
    assert found[0].path.endswith("config.py")


def test_r009_flags_knob_missing_from_readme(tmp_path):
    found = _findings(tmp_path, _r009_files(readme="nothing here\n"),
                      rule="R009")
    assert {("metrics_file" in f.message or "factor_num" in f.message)
            for f in found} == {True}
    assert all("README" in f.message for f in found)


def test_r009_flags_unknown_sample_cfg_key(tmp_path):
    found = _findings(tmp_path, _r009_files(
        sample=_SAMPLE_OK + "factr_num = 9\n"), rule="R009")
    assert len(found) == 1
    assert "factr_num" in found[0].message
    assert found[0].path.endswith("sample.cfg")
    assert found[0].line == 4  # the misspelled assignment's line


def test_r009_flags_inconsistent_env_fallback(tmp_path):
    found = _findings(tmp_path, _r009_files(extra={
        "fast_tffm_tpu/cli.py": """\
            import os
            def read():
                ok = os.environ.get("FM_METRICS_FILE")
                bad = os.environ.get("FM_METRIC_FILE")
                return ok, bad
        """,
        "sample.cfg2": ""}), rule="R009")
    assert len(found) == 1
    assert "FM_METRIC_FILE" in found[0].message


def test_r009_flags_stale_cfg_attribute_read(tmp_path):
    found = _findings(tmp_path, _r009_files(extra={
        "fast_tffm_tpu/user.py": """\
            def go(cfg):
                a = cfg.factor_num
                b = cfg.row_dim
                return a, b, cfg.metrics_flle
        """}), rule="R009")
    assert len(found) == 1
    assert "metrics_flle" in found[0].message


# --- R010: unwrapped hot-path IO ------------------------------------------

def _pipe(body):
    return {"fast_tffm_tpu/data/pipeline.py": body}


def test_r010_flags_raw_open_in_pipeline(tmp_path):
    found = _findings(tmp_path, _pipe("""\
        def read(path):
            with open(path) as fh:
                return fh.read()
    """), rule="R010")
    assert len(found) == 1
    assert "utils/retry" in found[0].message


def test_r010_allows_policy_aware_conditional_form(tmp_path):
    found = _findings(tmp_path, _pipe("""\
        from fast_tffm_tpu.utils.retry import open_with_retry
        def read(path, retry=None):
            fh = (open(path) if retry is None else
                  open_with_retry(path, policy=retry))
            return fh
    """), rule="R010")
    assert found == []


def test_r010_allows_explicit_oserror_contract(tmp_path):
    found = _findings(tmp_path, _pipe("""\
        def read_sidecar(path):
            try:
                with open(path) as fh:
                    return fh.read()
            except OSError:
                return None
    """), rule="R010")
    assert found == []


def test_r010_allows_retrying_decorator(tmp_path):
    found = _findings(tmp_path, _pipe("""\
        from fast_tffm_tpu.utils.retry import retrying
        @retrying("sidecar_read")
        def read(path):
            with open(path) as fh:
                return fh.read()
    """), rule="R010")
    assert found == []


def test_r010_scopes_to_hot_modules(tmp_path):
    found = _findings(tmp_path, {"fast_tffm_tpu/metrics.py": """\
        def read(path):
            return open(path).read()
    """}, rule="R010")
    assert found == []


def test_r010_respects_pragma(tmp_path):
    found = _findings(tmp_path, _pipe("""\
        def read(path):
            # fmlint: disable=R010 -- caller owns the OSError contract
            with open(path) as fh:
                return fh.read()
    """), rule="R010")
    assert found == []


# --- baseline + json -------------------------------------------------------

def _one_finding_project(tmp_path):
    # Real package shape (__init__.py present) so the project root —
    # which baseline keys are relative to — lands at tmp_path, the
    # way the repo surface roots at the repo.
    return _project(tmp_path, {
        "fast_tffm_tpu/__init__.py": "",
        "fast_tffm_tpu/data/__init__.py": "",
        "fast_tffm_tpu/data/pipeline.py": """\
            def read(path):
                return open(path).read()
        """})


def test_baseline_suppresses_recorded_findings(tmp_path):
    root, _ = _one_finding_project(tmp_path)
    found = run_paths([root])
    assert [f.rule for f in found] == ["R010"]
    bl = tmp_path / "baseline.txt"
    write_baseline(found, str(bl), root)
    assert run_paths([root], baseline=str(bl)) == []


def test_baseline_does_not_absorb_new_findings(tmp_path):
    """Entries are line-free but counted: one recorded finding absorbs
    one occurrence, a second identical one still fails the gate."""
    root, _ = _one_finding_project(tmp_path)
    found = run_paths([root])
    bl = tmp_path / "baseline.txt"
    write_baseline(found, str(bl), root)
    p = tmp_path / "fast_tffm_tpu" / "data" / "pipeline.py"
    p.write_text(p.read_text()
                 + "\ndef read2(path):\n    return open(path).read()\n")
    remaining = run_paths([root], baseline=str(bl))
    assert [f.rule for f in remaining] == ["R010"]


def test_baseline_survives_line_shifts(tmp_path):
    root, _ = _one_finding_project(tmp_path)
    bl = tmp_path / "baseline.txt"
    write_baseline(run_paths([root]), str(bl), root)
    p = tmp_path / "fast_tffm_tpu" / "data" / "pipeline.py"
    p.write_text("# a comment pushing everything down\n\n\n"
                 + p.read_text())
    assert run_paths([root], baseline=str(bl)) == []


def test_cli_json_output(tmp_path, capsys):
    root, paths = _one_finding_project(tmp_path)
    assert main(["--json", "--no-baseline", root]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["count"] == 1
    assert out["findings"][0]["rule"] == "R010"
    assert out["findings"][0]["path"].endswith("pipeline.py")


def test_cli_update_baseline_round_trip(tmp_path, capsys):
    root, _ = _one_finding_project(tmp_path)
    bl = tmp_path / "baseline.txt"
    assert main(["--baseline", str(bl), "--update-baseline",
                 root]) == 0
    capsys.readouterr()
    # NOTE: the committed repo baseline stores paths relative to the
    # repo root; this round-trip exercises an explicit --baseline file
    # against the same surface it was recorded from.
    assert main(["--baseline", str(bl), root]) == 0


# --- R012: health-catalog drift --------------------------------------------

_ATT_OK = """\
    HEALTH_KINDS = frozenset({"stalled", "gate_held"})
"""

_EMITTERS = """\
    def watchdog(sink):
        sink.emit("health", {"status": "stalled", "step": 3})

    def gate(tel):
        fields = {"status": "gate_held", "auc": 0.2}
        tel.sink.emit("health", fields)
"""


def _r012_files(att=_ATT_OK, emitters=_EMITTERS,
                readme="catalog: stalled and gate_held rows\n"):
    return {"fast_tffm_tpu/obs/attribution.py": att,
            "fast_tffm_tpu/obs/emitters.py": emitters,
            "README.md": readme}


def test_r012_clean_when_catalog_covers_emits(tmp_path):
    assert _findings(tmp_path, _r012_files(), rule="R012") == []


def test_r012_flags_unmapped_emitted_kind(tmp_path):
    found = _findings(tmp_path, _r012_files(
        emitters=_EMITTERS + """\

    def rogue(sink):
        sink.emit("health", {"status": "zombie", "step": 1})
""",
        readme="stalled gate_held zombie\n"), rule="R012")
    assert len(found) == 1
    assert "zombie" in found[0].message
    assert "HEALTH_KINDS" in found[0].message
    assert found[0].path.endswith("emitters.py")


def test_r012_flags_missing_readme_row(tmp_path):
    found = _findings(tmp_path, _r012_files(
        readme="only stalled is documented\n"), rule="R012")
    assert len(found) == 1
    assert "gate_held" in found[0].message
    assert "README" in found[0].message


def test_r012_flags_stale_catalog_entry(tmp_path):
    found = _findings(tmp_path, _r012_files(
        att='HEALTH_KINDS = frozenset({"stalled", "gate_held", '
            '"ghost"})\n',
        readme="stalled gate_held ghost\n"), rule="R012")
    assert len(found) == 1
    assert "ghost" in found[0].message
    assert "stale" in found[0].message
    assert found[0].path.endswith("attribution.py")


def test_r012_ignores_status_dicts_without_health_emit(tmp_path):
    """A {"status": ...} dict that is not a health-emit PAYLOAD is not
    a health kind — whether it lives in a non-emitting scope (an HTTP
    stats payload) or right beside an emit in the same function (the
    scan anchors on the emit call's argument, not the whole scope)."""
    found = _findings(tmp_path, _r012_files(
        emitters=_EMITTERS + """\

    def stats():
        return {"status": "ok", "uptime": 1.0}

    def emit_and_report(sink):
        sink.emit("health", {"status": "stalled"})
        return {"status": "weird_unrelated"}
"""), rule="R012")
    assert found == []


def test_r012_one_readme_finding_per_kind(tmp_path):
    """A kind emitted from several sites with its README row missing
    is ONE finding (the missing artifact is the catalog row), while
    the HEALTH_KINDS mapping check stays per-site."""
    found = _findings(tmp_path, _r012_files(
        emitters=_EMITTERS + """\

    def again(sink):
        sink.emit("health", {"status": "gate_held", "step": 9})
""",
        readme="only stalled is documented\n"), rule="R012")
    assert len(found) == 1
    assert "gate_held" in found[0].message
    assert "README" in found[0].message


def test_r012_pragma_escape(tmp_path):
    found = _findings(tmp_path, _r012_files(
        emitters=_EMITTERS + """\

    def experimental(sink):
        sink.emit("health", {"status": "wip_kind"})  # fmlint: disable=R012 -- staged rollout, catalog lands next PR
""",
        readme="stalled gate_held wip_kind\n"), rule="R012")
    assert found == []


def test_repo_baseline_is_empty():
    """The adoption sweep left ZERO accepted findings: the committed
    baseline must stay empty so any new finding fails the gate."""
    bl = os.path.join(REPO, "tools", "fmlint", "baseline.txt")
    from tools.fmlint.core import load_baseline
    assert load_baseline(bl) == []
