"""fmlint — static checks for this repo's performance and
cluster-correctness invariants.

The invariants live in prose (README "Device-link sync pathology",
the PR 3-5 robustness postmortems); this package makes them
machine-checked and wires them into the tier-1 test run
(tests/test_fmlint.py). Two layers:

Per-file rules (stdlib-``ast``, tools/fmlint/rules.py):

R001  per-scalar device fetch in a hot-loop module (``float``/``int``
      in a loop body, any ``.item()``) — one synchronous scalar
      materialization in the hot stream stalls async dispatch until
      the device has caught up.
R002  bare ``print(`` in a hot-loop module.
R003  raw ``perf_counter()`` pairs in hot loops (use obs.trace.span).
R004  broad swallow-and-continue handlers in hot modules.
R005  checkpoint deletion outside checkpoint.py (quarantine, never
      delete).
R006  bare blocking collective outside ``guarded_collective()``.
R999  file fails to parse (fails the gate for the whole surface).

Whole-program rules (tools/fmlint/project.py builds one parsed,
import-resolved, call-graph-summarized model of the full lint
surface; tools/fmlint/xrules.py consumes it):

R007  a collective reachable (transitively) on only one arm of a
      rank-conditioned branch — the multi-host deadlock.
R008  shared state written from a provably thread-reachable function
      without holding a lock.
R009  config/knob drift: knobs missing from sample.cfg/README,
      unknown sample.cfg keys, inconsistent ``FM_*`` env fallbacks,
      stale ``cfg.<attr>`` reads.
R010  raw ``open()`` on pipeline/checkpoint hot paths with no
      utils/retry wrapper and no explicit OSError contract.

Deliberate exceptions carry a justified pragma:

    x = float(probe)  # fmlint: disable=R001 -- pre-loop link probe

A whole-line pragma comment suppresses the entire next statement; a
pragma without a ``--`` justification is itself reported (R000).
``tools/fmlint/baseline.txt`` holds the committed baseline for
gradual adoption (``--update-baseline`` / ``--baseline``); ``--json``
emits machine-readable findings.

Run: ``python -m tools.fmlint`` (whole repo surface: fast_tffm_tpu/,
tools/, run_tffm.py) or pass files/dirs.
"""

from tools.fmlint.core import Finding, main, run_file, run_paths

__all__ = ["Finding", "main", "run_file", "run_paths"]
