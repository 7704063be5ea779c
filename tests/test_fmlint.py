"""tools/fmlint: the hot-loop device-fetch/print rules, suppression
grammar, and the repo-wide lint gate (this file IS the tier-1 wiring —
a hot-loop regression fails the suite here)."""

import os
import textwrap

import pytest

from tools.fmlint.core import run_file, run_paths
from tools.fmlint.rules import is_hot_module


def _hot_file(tmp_path, body):
    """Write ``body`` at a path the rules treat as a hot module."""
    d = tmp_path / "fast_tffm_tpu"
    d.mkdir(exist_ok=True)
    p = d / "train.py"
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_repo_surface_is_clean():
    """THE lint gate: the full default surface — fast_tffm_tpu/,
    tools/ (fmlint lints itself), run_tffm.py — must have
    zero findings under every rule, per-file AND whole-program
    (deliberate exceptions carry justified pragmas; the committed
    baseline is empty). R999 parse failures anywhere on this surface
    fail here too."""
    from tools.fmlint.core import default_baseline_path, default_paths
    findings = run_paths(default_paths(),
                         baseline=default_baseline_path())
    assert findings == [], "\n".join(f.render() for f in findings)


def test_default_surface_includes_tools_and_cli():
    """ISSUE 7 satellite: the no-argument lint surface reaches beyond
    the package to the tools and CLI entry points."""
    from tools.fmlint.core import default_paths
    names = [os.path.basename(p) for p in default_paths()]
    assert names == ["fast_tffm_tpu", "tools", "run_tffm.py"]


def test_collect_files_is_deterministic_and_sorted(tmp_path):
    """ISSUE 7 satellite: finding order (and therefore baseline
    diffs) must be stable across filesystems — both the directory
    descent and per-directory file order are sorted."""
    from tools.fmlint.core import collect_files
    for rel in ("b/zz.py", "b/aa.py", "a/x.py", "c/__pycache__/j.py",
                "top.py"):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text("x = 1\n")
    got = [os.path.relpath(f, tmp_path)
           for f in collect_files([str(tmp_path)])]
    assert got == ["top.py", "a/x.py", "b/aa.py", "b/zz.py"]
    assert got == [os.path.relpath(f, tmp_path)
                   for f in collect_files([str(tmp_path)])]


def test_is_hot_module_scope():
    assert is_hot_module("x/fast_tffm_tpu/train.py")
    assert is_hot_module("x/fast_tffm_tpu/predict.py")
    assert is_hot_module("x/fast_tffm_tpu/data/pipeline.py")
    assert is_hot_module("x/fast_tffm_tpu/obs/sink.py")
    assert not is_hot_module("x/fast_tffm_tpu/metrics.py")
    assert not is_hot_module("x/run_tffm.py")
    assert not is_hot_module("x/tools/fmstat/__init__.py")


def test_r001_flags_scalar_fetch_in_loop(tmp_path):
    path = _hot_file(tmp_path, """\
        def run(it, step):
            for batch in it:
                loss = step(batch)
                print_loss = float(loss)
            return loss
    """)
    found = run_file(path)
    assert [f.rule for f in found] == ["R001"]
    assert found[0].line == 4


def test_r001_flags_item_anywhere(tmp_path):
    path = _hot_file(tmp_path, """\
        def read(loss):
            return loss.item()
    """)
    found = run_file(path)
    assert [f.rule for f in found] == ["R001"]


def test_r001_allows_fetch_outside_loops(tmp_path):
    path = _hot_file(tmp_path, """\
        def final(loss):
            return float(loss)
    """)
    assert run_file(path) == []


def test_r002_flags_bare_print(tmp_path):
    path = _hot_file(tmp_path, """\
        def log(x):
            print(x)
    """)
    found = run_file(path)
    assert [f.rule for f in found] == ["R002"]


def test_rules_scope_to_hot_modules_only(tmp_path):
    p = tmp_path / "other.py"
    p.write_text("def f(it):\n    for x in it:\n        print(float(x))\n")
    assert run_file(str(p)) == []


def test_inline_pragma_suppresses_with_justification(tmp_path):
    path = _hot_file(tmp_path, """\
        def run(it):
            for x in it:
                v = float(x)  # fmlint: disable=R001 -- host value
            return v
    """)
    assert run_file(path) == []


def test_wholeline_pragma_covers_next_statement(tmp_path):
    path = _hot_file(tmp_path, """\
        def run(it, f):
            for x in it:
                # fmlint: disable=R001 -- host allgather results
                v = f(int(x[0]),
                      int(x[1]),
                      int(x[2]))
            return v
    """)
    assert run_file(path) == []


def test_pragma_without_justification_is_r000(tmp_path):
    path = _hot_file(tmp_path, """\
        def run(it):
            for x in it:
                v = float(x)  # fmlint: disable=R001
            return v
    """)
    rules = sorted(f.rule for f in run_file(path))
    # the naked pragma is reported AND does not suppress
    assert rules == ["R000", "R001"]


def test_disable_file_pragma(tmp_path):
    path = _hot_file(tmp_path, """\
        # fmlint: disable-file=R002 -- exercise harness, prints wanted
        def a(x):
            print(x)
        def b(it):
            for v in it:
                print(v)
    """)
    assert run_file(path) == []


def test_syntax_error_reports_r999(tmp_path):
    path = _hot_file(tmp_path, "def broken(:\n")
    found = run_file(path)
    assert [f.rule for f in found] == ["R999"]


def test_cli_main(tmp_path, capsys):
    from tools.fmlint.core import main
    bad = _hot_file(tmp_path, """\
        def run(it):
            for x in it:
                print(float(x))
    """)
    assert main([bad]) == 1
    out = capsys.readouterr()
    assert "R001" in out.out and "R002" in out.out
    ok = tmp_path / "clean.py"
    ok.write_text("x = 1\n")
    assert main([str(ok)]) == 0


def test_r003_flags_perf_counter_in_loop(tmp_path):
    """ISSUE 3 satellite: hot-loop timing should go through the
    no-op-when-inactive obs.trace.span(), not hand-rolled
    perf_counter pairs."""
    path = _hot_file(tmp_path, """\
        import time
        def run(it):
            for x in it:
                t0 = time.perf_counter()
                do(x)
                dt = time.perf_counter() - t0
    """)
    found = run_file(path)
    assert [f.rule for f in found] == ["R003", "R003"]
    assert [f.line for f in found] == [4, 6]


def test_r003_allows_perf_counter_outside_loops(tmp_path):
    path = _hot_file(tmp_path, """\
        import time
        def stamp():
            return time.perf_counter()
    """)
    assert run_file(path) == []


def test_r003_flags_bare_name_and_respects_pragma(tmp_path):
    path = _hot_file(tmp_path, """\
        from time import perf_counter
        def run(it):
            for x in it:
                # fmlint: disable=R003 -- feeds an always-on histogram
                t0 = perf_counter()
                t1 = perf_counter()
    """)
    found = run_file(path)
    assert [(f.rule, f.line) for f in found] == [("R003", 6)]


def test_r004_flags_swallowed_broad_except(tmp_path):
    """ISSUE 4 satellite: bare `except Exception: pass` in hot modules
    turns failures the fault-tolerance layer should count/surface into
    silence."""
    path = _hot_file(tmp_path, """\
        def run(it):
            for x in it:
                try:
                    do(x)
                except Exception:
                    pass
    """)
    found = run_file(path)
    assert [f.rule for f in found] == ["R004"]
    assert found[0].line == 5


def test_r004_flags_bare_except_continue(tmp_path):
    path = _hot_file(tmp_path, """\
        def run(it):
            for x in it:
                try:
                    do(x)
                except:
                    continue
    """)
    assert [f.rule for f in run_file(path)] == ["R004"]


def test_r004_flags_broad_tuple(tmp_path):
    path = _hot_file(tmp_path, """\
        def run(x):
            try:
                do(x)
            except (ValueError, Exception):
                pass
    """)
    assert [f.rule for f in run_file(path)] == ["R004"]


def test_r004_allows_narrow_handlers(tmp_path):
    path = _hot_file(tmp_path, """\
        def run(x):
            try:
                do(x)
            except (OSError, RuntimeError):
                pass
    """)
    assert run_file(path) == []


def test_r004_allows_handled_broad_except(tmp_path):
    path = _hot_file(tmp_path, """\
        def run(x, log):
            try:
                do(x)
            except Exception:
                log.exception("do failed")
    """)
    assert run_file(path) == []


def test_r004_respects_pragma(tmp_path):
    path = _hot_file(tmp_path, """\
        def run(x):
            try:
                do(x)
            except Exception:  # fmlint: disable=R004 -- must outlive
                pass
    """)
    assert run_file(path) == []


def _any_file(tmp_path, body, name="cleanup.py"):
    """R005 applies to every linted module, not just hot ones."""
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_r005_flags_rmtree_on_ckpt_path(tmp_path):
    """ISSUE 5 satellite: quarantine-not-delete is the state-plane
    invariant — direct deletion of checkpoint state outside
    checkpoint.py is a finding."""
    path = _any_file(tmp_path, """\
        import shutil
        def clean(ckpt_dir):
            shutil.rmtree(ckpt_dir)
    """)
    found = run_file(path)
    assert [f.rule for f in found] == ["R005"]
    assert "quarantine" in found[0].message


def test_r005_flags_os_remove_on_ckpt_literal(tmp_path):
    path = _any_file(tmp_path, """\
        import os
        def clean(model):
            os.remove(model + ".ckpt/manifest-3.json")
    """)
    assert [f.rule for f in run_file(path)] == ["R005"]


def test_r005_flags_step_dir_unlink(tmp_path):
    path = _any_file(tmp_path, """\
        import os
        def clean(step_dir):
            os.unlink(step_dir)
    """)
    assert [f.rule for f in run_file(path)] == ["R005"]


def test_r005_allows_checkpoint_py_itself(tmp_path):
    path = _any_file(tmp_path, """\
        import shutil
        def clean(ckpt_dir):
            shutil.rmtree(ckpt_dir)
    """, name="checkpoint.py")
    assert run_file(path) == []


def test_r005_allows_non_ckpt_deletes(tmp_path):
    path = _any_file(tmp_path, """\
        import os
        def clean(part_file):
            os.remove(part_file)
    """)
    assert run_file(path) == []


def test_r005_respects_pragma(tmp_path):
    path = _any_file(tmp_path, """\
        import shutil
        def gc(ckpt_dir):
            # fmlint: disable=R005 -- sanctioned operator gc path
            shutil.rmtree(ckpt_dir)
    """)
    assert run_file(path) == []


def _parallel_file(tmp_path, body, name="sharded.py"):
    """Write ``body`` at a path inside R006's cluster-critical scope."""
    d = tmp_path / "fast_tffm_tpu" / "parallel"
    d.mkdir(parents=True, exist_ok=True)
    p = d / name
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_r006_flags_bare_collectives(tmp_path):
    """ISSUE 6 satellite: a bare blocking collective outside
    guarded_collective() in a cluster-critical module is the
    hang-forever-on-a-dead-peer failure mode."""
    path = _parallel_file(tmp_path, """\
        from jax.experimental import multihost_utils
        def sync(x):
            fills = multihost_utils.process_allgather(x)
            v = multihost_utils.broadcast_one_to_all(x)
            multihost_utils.sync_global_devices("tag")
            return fills, v
    """)
    found = run_file(path)
    assert [f.rule for f in found] == ["R006", "R006", "R006"]
    assert "guarded_collective" in found[0].message


def test_r006_allows_passing_collective_as_argument(tmp_path):
    """The guarded form REFERENCES the collective without calling it —
    that must not be a finding, or the fix itself would be flagged."""
    path = _parallel_file(tmp_path, """\
        from jax.experimental import multihost_utils
        from fast_tffm_tpu.parallel.liveness import guarded_collective
        def sync(x):
            return guarded_collective(
                multihost_utils.process_allgather, x, label="x")
    """)
    assert run_file(path) == []


def test_r006_scope(tmp_path):
    body = """\
        from jax.experimental import multihost_utils
        def sync(x):
            return multihost_utils.process_allgather(x)
    """
    # checkpoint.py and train.py are in scope...
    d = tmp_path / "fast_tffm_tpu"
    d.mkdir(exist_ok=True)
    for name in ("checkpoint.py", "train.py"):
        p = d / name
        p.write_text(textwrap.dedent(body))
        assert [f.rule for f in run_file(str(p))] == ["R006"], name
    # ...the guard's own implementation and non-cluster modules are not
    assert run_file(_parallel_file(tmp_path, body,
                                   name="liveness.py")) == []
    other = d / "metrics.py"
    other.write_text(textwrap.dedent(body))
    assert run_file(str(other)) == []


def test_r006_respects_pragma(tmp_path):
    path = _parallel_file(tmp_path, """\
        from jax.experimental import multihost_utils
        def sync(x):
            # fmlint: disable=R006 -- bring-up path, no guard yet
            return multihost_utils.process_allgather(x)
    """)
    assert run_file(path) == []


# --- pragma edge cases (ISSUE 7 satellite) ---------------------------------

def test_wholeline_pragma_above_decorated_function(tmp_path):
    """A whole-line pragma above a DECORATED function suppresses the
    whole function statement: the decorator is an expression, not a
    statement, so the next statement span is the full def (decorators
    included in neither — the span runs def..end of body)."""
    path = _hot_file(tmp_path, """\
        import functools
        # fmlint: disable=R001 -- whole helper reads host values
        @functools.lru_cache(maxsize=8)
        def read(loss, it):
            for x in it:
                v = float(x)
            return v + loss.item()
    """)
    assert run_file(path) == []


def test_wholeline_pragma_covers_finding_on_last_span_line(tmp_path):
    """Multi-line call spans: the pragma covers findings anchored on
    ANY line of the next statement, including the last."""
    path = _hot_file(tmp_path, """\
        def run(it, f):
            for x in it:
                # fmlint: disable=R001 -- host tuple unpack
                v = f(x[0],
                      x[1],
                      int(x[2]))
            return v
    """)
    assert run_file(path) == []


def test_disable_file_without_justification_is_r000(tmp_path):
    """``disable-file=`` without a ``--`` rationale is itself reported
    AND does not suppress anything."""
    path = _hot_file(tmp_path, """\
        # fmlint: disable-file=R002
        def log(x):
            print(x)
    """)
    rules = sorted(f.rule for f in run_file(path))
    assert rules == ["R000", "R002"]


def test_r999_fails_gate_for_expanded_surface(tmp_path):
    """A syntax error anywhere on a linted surface (e.g. a tools/
    module) surfaces as R999 through the whole-program runner and
    fails the gate."""
    d = tmp_path / "tools" / "fmthing"
    d.mkdir(parents=True)
    (d / "__init__.py").write_text("def broken(:\n")
    (tmp_path / "ok.py").write_text("x = 1\n")
    findings = run_paths([str(tmp_path)])
    assert [f.rule for f in findings] == ["R999"]
    assert findings[0].path.endswith("__init__.py")


def test_r011_flags_raw_table_index(tmp_path):
    """ISSUE 12 satellite: a raw ``table[ids]`` outside lookup.py/
    vocab/ bypasses the slot-indirection seam — under vocab_mode =
    admit it reads rows the slot map may have reassigned or reset."""
    path = _any_file(tmp_path, """\
        def gather(table, ids):
            return table[ids]
    """)
    found = run_file(path)
    assert [f.rule for f in found] == ["R011"]
    assert "slot-indirection" in found[0].message


def test_r011_flags_attribute_table_index(tmp_path):
    path = _any_file(tmp_path, """\
        def gather(self, ids):
            return self.table[ids]
    """)
    assert [f.rule for f in run_file(path)] == ["R011"]


def test_r011_allows_layout_slices_and_fixed_rows(tmp_path):
    """Slices (checkpoint layout trims) and constant rows — negative
    included (the dead tail row) — address LAYOUT, not id routing."""
    path = _any_file(tmp_path, """\
        def trim(table, n):
            head = table[:n]
            row0 = table[0]
            tail = table[-1]
            block = table[0:4, :]
            corner = table[-1, :]
            return head, row0, tail, block, corner
    """)
    assert run_file(path) == []


def test_r011_exempts_lookup_and_vocab_modules(tmp_path):
    """lookup.py and vocab/ ARE the seam — raw indexing there is the
    implementation, not a bypass."""
    body = """\
        def gather(table, ids):
            return table[ids]
    """
    d = tmp_path / "fast_tffm_tpu"
    d.mkdir()
    import textwrap as _tw
    (d / "lookup.py").write_text(_tw.dedent(body))
    v = d / "vocab"
    v.mkdir()
    (v / "table.py").write_text(_tw.dedent(body))
    assert run_file(str(d / "lookup.py")) == []
    assert run_file(str(v / "table.py")) == []


def test_r011_respects_pragma(tmp_path):
    path = _any_file(tmp_path, """\
        def step(table, uniq_ids):
            # fmlint: disable=R011 -- jitted step below the slot seam
            return table[uniq_ids]
    """)
    assert run_file(path) == []


def test_r013_flags_adhoc_device_put_in_dispatch_modules(tmp_path):
    """ISSUE 15 satellite: a raw ``jax.device_put`` in a train/predict/
    scoring/serve module bypasses the wire-format encoder — the packed
    layout, the double buffer, and the h2d byte accounting all miss
    those arrays."""
    path = _hot_file(tmp_path, """\
        import jax
        def dispatch(batch_args):
            return jax.device_put(batch_args)
    """)
    found = [f for f in run_file(path) if f.rule == "R013"]
    assert len(found) == 1
    assert "wire" in found[0].message


def test_r013_flags_bare_imported_device_put(tmp_path):
    path = _hot_file(tmp_path, """\
        from jax import device_put
        def dispatch(args):
            return device_put(args)
    """)
    assert [f.rule for f in run_file(path) if f.rule == "R013"] \
        == ["R013"]


def test_r013_allows_encoder_method_and_other_modules(tmp_path):
    """The sanctioned spelling — the wire encoder's own method — and
    any module outside the dispatch surface pass."""
    path = _hot_file(tmp_path, """\
        def dispatch(enc, wb):
            return enc.device_put(wb)
    """)
    assert [f.rule for f in run_file(path) if f.rule == "R013"] == []
    other = _any_file(tmp_path, """\
        import jax
        def elsewhere(x):
            return jax.device_put(x)
    """, name="helper.py")
    assert [f.rule for f in run_file(other) if f.rule == "R013"] == []


def test_r013_respects_pragma(tmp_path):
    path = _hot_file(tmp_path, """\
        import jax
        def probe():
            # fmlint: disable=R013 -- one-scalar link probe, not a batch
            return jax.device_put(0.0)
    """)
    assert [f.rule for f in run_file(path) if f.rule == "R013"] == []


def test_r018_flags_adhoc_memory_stats(tmp_path):
    """ISSUE 18 satellite: device-memory introspection outside the
    obs/memory seam bypasses the unmeasured-is-None policy, the CPU
    opt-out, and the FM_FAKE_HBM_BYTES test injection."""
    path = _any_file(tmp_path, """\
        import jax

        def probe(dev):
            stats = dev.memory_stats()
            arrays = jax.live_arrays()
            return stats, arrays
    """, name="probe.py")
    found = [f for f in run_file(path) if f.rule == "R018"]
    assert len(found) == 2
    assert "obs/memory.device_memory_stats" in found[0].message


def test_r018_exempts_the_seam_module(tmp_path):
    d = tmp_path / "fast_tffm_tpu" / "obs"
    d.mkdir(parents=True)
    p = d / "memory.py"
    p.write_text(textwrap.dedent("""\
        def device_memory_stats(dev):
            return dev.memory_stats()
    """))
    assert [f.rule for f in run_file(str(p))
            if f.rule == "R018"] == []


def test_r018_respects_pragma(tmp_path):
    path = _any_file(tmp_path, """\
        def raw_probe(dev):
            # fmlint: disable=R018 -- leak hunt, wants raw runtime stats
            return dev.memory_stats()
    """, name="probe.py")
    assert [f.rule for f in run_file(path) if f.rule == "R018"] == []
