"""fmlint whole-program rules (R007-R012, R014-R017) over
tools/fmlint/project.py.

These are the bug classes PRs 3-5's reviews kept catching by hand —
whole-program properties no per-file syntactic rule can see:

R007  divergent collective: a call that may (transitively) execute a
      blocking collective is reachable under one arm of a branch
      conditioned on process rank, with no matching collective on the
      other arm — the multi-host deadlock (peers never post the
      matching call; the exact bug PR 4's review caught in the restore
      walk-back).
R008  unsynchronized shared mutation: an instance attribute or module
      global written from a function the thread summary proves can run
      on a spawned thread, without holding a lock — the data race that
      multiplies as the perf roadmap adds threads.
R009  config/knob drift: every knob in config.py's section tables must
      appear in sample.cfg AND the README; FM_* env fallbacks must map
      to a real knob name; unknown keys in sample.cfg and unknown
      ``cfg.<attr>`` reads are findings — the doc/schema rot the
      [Cluster]/[Train] knob additions kept reintroducing.
R010  unwrapped hot-path IO: a raw ``open()`` in the pipeline/
      checkpoint hot modules that neither goes through utils/retry
      (``open_with_retry`` / ``retry_io`` / ``@retrying``) nor sits
      under an explicit OSError-family handler — IO with no failure
      contract on exactly the paths transient NFS errors hit.
R012  health-catalog drift: every ``health: <kind>`` event emitted
      anywhere must appear in obs/attribution.HEALTH_KINDS (the fmstat
      verdict/notes mapping) AND in the README's health-event catalog;
      a catalog entry nothing emits is stale — the drift gate that
      keeps "fmstat explains every event the system can write" true
      as subsystems grow (the R009 pattern applied to the health
      stream).
R014  protocol divergence (the model checker): the ordered collective
      sequence a function executes must be rank-invariant — a branch/
      loop/try arm conditioned on a LOCAL (per-process) value whose
      arms post different collective sequences is the walk-back
      deadlock class PR 4's review caught by hand; values routed
      through a collective are agreed and sanitize the condition.
R015  thread-reachable collective: a blocking collective reachable
      from a ``Thread(target=...)`` entry point — collective order
      across ranks is only defined for the driver loop.
R016  lock-order cycle: the ``with <lock>`` nesting graph (direct and
      through resolved calls) must stay acyclic, or two threads
      deadlock on the inverted pair.
R017  lock across blocking op: a collective or device fetch executing
      while a lock is held — one stalled peer turns the lock into a
      cluster-wide stall.

Each rule returns standard Findings, so the pragma grammar and the
baseline mechanism apply unchanged. Precision policy: the engine's
summaries UNDERCLAIM (tools/fmlint/project.py docstring) — a finding
here is evidence, and the sweep fixing or pragma-justifying every one
is part of the rule's contract.
"""

from __future__ import annotations

import ast
import weakref
import configparser
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from tools.fmlint.core import Finding
from tools.fmlint.project import (COLLECTIVE_NAMES, FETCH_NAMES,
                                  FunctionInfo, Project, _dotted,
                                  collective_ops, resolve_call)

# --- shared helpers --------------------------------------------------------

_BLOCK_FIELDS = ("body", "orelse", "finalbody")


def _own_expr_nodes(stmt) -> Iterable[ast.AST]:
    """Every AST node belonging to ``stmt`` itself — headers and inline
    expressions — excluding nested statement blocks (those are walked
    as statements in their own right)."""
    for field, value in ast.iter_fields(stmt):
        if field in _BLOCK_FIELDS or field == "handlers":
            continue
        vals = value if isinstance(value, list) else [value]
        for v in vals:
            if isinstance(v, ast.AST):
                yield from ast.walk(v)


def _walk_skip_defs(node) -> Iterable[ast.AST]:
    """Walk ``node`` without descending into nested function/class
    bodies: defining a function executes nothing."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            stack.append(child)


# --- R007: divergent collective -------------------------------------------

_RANK_TOKENS = frozenset({"process_index", "process_id", "rank",
                          "shard_index"})


def _is_sanitizing(proj: Project, fn: FunctionInfo, expr) -> bool:
    """A value produced BY a collective is rank-uniform by
    construction — ``cand = self._broadcast_int(cand)`` is the
    agreement primitive, not a divergence source."""
    for n in ast.walk(expr):
        if not isinstance(n, ast.Call):
            continue
        base = None
        if isinstance(n.func, ast.Name):
            base = n.func.id
        elif isinstance(n.func, ast.Attribute):
            base = n.func.attr
        if base in COLLECTIVE_NAMES:
            return True
        callee = resolve_call(proj, fn, n.func)
        if callee is not None and proj.collectives_of(callee):
            return True
    return False


def _taint_assigns(fn: FunctionInfo
                   ) -> List[Tuple[int, ast.AST, ast.AST]]:
    """(lineno, target, value) for every simple assignment in source
    order. Tuple assignments pair elementwise so ``p, P =
    jax.process_index(), jax.process_count()`` can taint only ``p``."""
    out: List[Tuple[int, ast.AST, ast.AST]] = []
    for n in _walk_skip_defs(fn.node):
        if isinstance(n, ast.Assign) and len(n.targets) == 1:
            t, v = n.targets[0], n.value
            if (isinstance(t, ast.Tuple) and isinstance(v, ast.Tuple)
                    and len(t.elts) == len(v.elts)):
                out.extend((n.lineno, te, ve)
                           for te, ve in zip(t.elts, v.elts))
            else:
                out.append((n.lineno, t, v))
    return sorted(out, key=lambda x: x[0])


def _tainted_at(proj: Project, fn: FunctionInfo,
                assigns: Sequence[Tuple[int, ast.AST, ast.AST]],
                line: int) -> Set[str]:
    """Replay assignments in source order up to ``line``: a value
    mentioning a rank token (or an already-tainted name) taints its
    target — ``proc0 = jax.process_index() == 0`` — and a value routed
    through a collective KILLS the taint (the broadcast result is the
    agreed, rank-uniform value). Linear source order stands in for
    control flow; good enough for the assign-then-branch shapes this
    rule polices."""
    tainted: Set[str] = set()
    for lineno, t, v in assigns:
        if lineno >= line:
            break
        if not isinstance(t, ast.Name):
            continue
        if _is_sanitizing(proj, fn, v):
            tainted.discard(t.id)
        elif _mentions_rank(v, tainted):
            tainted.add(t.id)
    return tainted


def _mentions_rank(expr, tainted: Set[str] = frozenset()) -> bool:
    for n in ast.walk(expr):
        if isinstance(n, ast.Name) and (n.id in _RANK_TOKENS
                                        or n.id in tainted):
            return True
        if isinstance(n, ast.Attribute) and n.attr in _RANK_TOKENS:
            return True
    return False


def _arm_collectives(proj: Project, fn: FunctionInfo,
                     stmts: Sequence[ast.stmt]) -> Set[str]:
    """Collective kinds that MAY execute somewhere in ``stmts``:
    direct calls plus anything the call graph proves a callee may
    reach."""
    kinds: Set[str] = set()
    for stmt in stmts:
        for n in _walk_skip_defs(stmt):
            if not isinstance(n, ast.Call):
                continue
            base = None
            if isinstance(n.func, ast.Name):
                base = n.func.id
            elif isinstance(n.func, ast.Attribute):
                base = n.func.attr
            if base in COLLECTIVE_NAMES:
                kinds.add(base)
            callee = resolve_call(proj, fn, n.func)
            if callee is not None:
                kinds |= proj.collectives_of(callee)
    return kinds


def _terminates(stmts: Sequence[ast.stmt]) -> bool:
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Continue, ast.Break))


def r007_divergent_collective(proj: Project) -> List[Finding]:
    found: List[Finding] = []
    for fn in proj.functions.values():
        assigns = _taint_assigns(fn)
        for block in _statement_blocks(fn.node):
            for i, stmt in enumerate(block):
                if not isinstance(stmt, ast.If):
                    continue
                tainted = _tainted_at(proj, fn, assigns, stmt.lineno)
                if not _mentions_rank(stmt.test, tainted):
                    continue
                arm_t: List[ast.stmt] = list(stmt.body)
                arm_f: List[ast.stmt] = list(stmt.orelse)
                tail = list(block[i + 1:])
                # An arm that returns/raises diverts the OTHER arm
                # into the block's tail: `if rank != 0: return` then a
                # collective below is rank-divergent too.
                if _terminates(arm_t) and not _terminates(arm_f):
                    arm_f = arm_f + tail
                elif _terminates(arm_f) and not _terminates(arm_t):
                    arm_t = arm_t + tail
                kt = _arm_collectives(proj, fn, arm_t)
                kf = _arm_collectives(proj, fn, arm_f)
                diff = sorted((kt - kf) | (kf - kt))
                if not diff:
                    continue
                found.append(Finding(
                    "R007", fn.module.path, stmt.lineno,
                    f"collective(s) {', '.join(diff)} reachable on only "
                    "one arm of a rank-conditioned branch "
                    f"(in {fn.qualname.rsplit('.', 1)[-1]}): processes "
                    "on the other arm never post the matching call and "
                    "the cluster deadlocks; hoist the collective out "
                    "of the branch, give the other arm its matching "
                    "call, or justify with a pragma"))
    return found


def _statement_blocks(func_node) -> Iterable[List[ast.stmt]]:
    """Every statement list in the function body — the function's own
    blocks only, not nested defs'."""
    out: List[List[ast.stmt]] = []

    def visit_block(stmts: List[ast.stmt]):
        out.append(stmts)
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            for field in _BLOCK_FIELDS:
                sub = getattr(s, field, None)
                if sub:
                    visit_block(sub)
            for h in getattr(s, "handlers", []) or []:
                visit_block(h.body)

    visit_block(list(func_node.body))
    return out


# --- R008: unsynchronized shared mutation ----------------------------------

def r008_unsynchronized_shared_mutation(proj: Project) -> List[Finding]:
    found: List[Finding] = []
    for q in sorted(proj.thread_funcs):
        fn = proj.functions.get(q)
        if fn is None or fn.name == "__init__":
            continue
        for w in fn.shared_writes:
            if w.locked:
                continue
            found.append(Finding(
                "R008", fn.module.path, w.line,
                f"'{w.target}' is mutated in {fn.name}(), which the "
                "thread summary shows can run on a spawned thread, "
                "without holding a lock; serialize on the owning lock "
                "(`with self._lock:`), or justify a single-writer / "
                "GIL-atomic design with a pragma"))
    return found


# --- R009: config/knob drift ----------------------------------------------

_SECTION_BY_DICT = {"_GENERAL_KEYS": "General", "_TRAIN_KEYS": "Train",
                    "_SLO_KEYS": "SLO", "_VOCAB_KEYS": "Vocab",
                    "_PREDICT_KEYS": "Predict", "_SERVE_KEYS": "Serve",
                    "_CLUSTER_KEYS": "Cluster"}


def _config_schema(mod) -> Tuple[Dict[str, Dict[str, int]], Set[str]]:
    """From config.py's AST: per-section {knob: definition line} from
    the ``_*_KEYS`` tables, and the full FmConfig attribute surface
    (fields + properties/methods) for the cfg.<attr> read check."""
    sections: Dict[str, Dict[str, int]] = {}
    surface: Set[str] = set()
    for node in mod.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            section = _SECTION_BY_DICT.get(node.targets[0].id)
            if section and isinstance(node.value, ast.Dict):
                keys = sections.setdefault(section, {})
                for k in node.value.keys:
                    if isinstance(k, ast.Constant) and isinstance(
                            k.value, str):
                        keys[k.value] = k.lineno
        elif isinstance(node, ast.ClassDef) and node.name == "FmConfig":
            for item in node.body:
                if isinstance(item, ast.AnnAssign) and isinstance(
                        item.target, ast.Name):
                    surface.add(item.target.id)
                elif isinstance(item, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    surface.add(item.name)
    return sections, surface


def _word_in(text: str, word: str) -> bool:
    return re.search(rf"\b{re.escape(word)}\b", text) is not None


def _cfg_key_line(cfg_text: str, section: str, key: str) -> int:
    """Line of an assigned (non-comment) key in an INI file, for
    anchoring unknown-key findings."""
    in_section = False
    for i, line in enumerate(cfg_text.splitlines(), start=1):
        s = line.strip()
        if s.startswith("["):
            in_section = s == f"[{section}]"
        elif in_section and re.match(
                rf"{re.escape(key)}\s*[=:]", s):
            return i
    return 0


def r009_config_drift(proj: Project) -> List[Finding]:
    cfg_mod = proj.module_at("fast_tffm_tpu/config.py")
    if cfg_mod is None:
        return []
    root = os.path.dirname(os.path.dirname(cfg_mod.path))
    sample_path = os.path.join(root, "sample.cfg")
    readme_path = os.path.join(root, "README.md")
    sections, surface = _config_schema(cfg_mod)
    knobs = {k for keys in sections.values() for k in keys}
    found: List[Finding] = []

    sample_text = readme_text = None
    if os.path.isfile(sample_path):
        with open(sample_path, "r", encoding="utf-8") as fh:
            sample_text = fh.read()
    if os.path.isfile(readme_path):
        with open(readme_path, "r", encoding="utf-8") as fh:
            readme_text = fh.read()

    # 1. every knob documented in sample.cfg and the README
    for section, keys in sorted(sections.items()):
        for knob, line in sorted(keys.items()):
            if sample_text is not None and not _word_in(sample_text,
                                                        knob):
                found.append(Finding(
                    "R009", cfg_mod.path, line,
                    f"[{section}] knob '{knob}' is not documented in "
                    "sample.cfg; add it (a value or a commented "
                    "default) so the quick-start config can't drift "
                    "from the schema"))
            if readme_text is not None and not _word_in(readme_text,
                                                        knob):
                found.append(Finding(
                    "R009", cfg_mod.path, line,
                    f"[{section}] knob '{knob}' is not documented in "
                    "the README; add it to the config-reference table"))

    # 2. unknown keys actually set in sample.cfg
    if sample_text is not None:
        cp = configparser.ConfigParser(
            inline_comment_prefixes=(";", "#"))
        try:
            cp.read_string(sample_text)
        except configparser.Error:
            cp = None
        if cp is not None:
            for section in cp.sections():
                known = sections.get(section)
                if known is None:
                    continue
                for key in cp.options(section):
                    if key not in known:
                        found.append(Finding(
                            "R009", sample_path,
                            _cfg_key_line(sample_text, section, key),
                            f"sample.cfg sets unknown [{section}] key "
                            f"'{key}' — config.py would reject it at "
                            "load time; fix the key or add it to the "
                            "schema"))

    # 3. FM_* env fallbacks must map to a real knob name
    for read in proj.env_reads:
        expect = read.var[len("FM_"):].lower()
        if expect not in knobs:
            found.append(Finding(
                "R009", read.path, read.line,
                f"env fallback '{read.var}' does not map to any config "
                f"knob ('{expect}' is not in config.py's section "
                "tables); FM_<KNOB> must stay consistent with its knob "
                "name"))

    # 4. cfg.<attr> reads against the FmConfig surface (package
    # modules only — `cfg` is FmConfig by convention there)
    pkg_prefix = os.path.dirname(cfg_mod.path) + os.sep
    extra_ok = {os.path.join(root, "run_tffm.py")}
    for read in proj.knob_reads:
        if read.obj != "cfg" or read.attr.startswith("_"):
            continue
        if not (read.path.startswith(pkg_prefix)
                or read.path in extra_ok):
            continue
        if surface and read.attr not in surface:
            found.append(Finding(
                "R009", read.path, read.line,
                f"cfg.{read.attr} is not a knob, property, or method "
                "of FmConfig — a renamed/removed knob left a stale "
                "reader (frozen dataclass: this raises at runtime)"))
    return found


# --- R010: unwrapped hot-path IO ------------------------------------------

R010_MODULE_SUFFIXES = ("fast_tffm_tpu/data/pipeline.py",
                        "fast_tffm_tpu/checkpoint.py")

# A handler for any of these has an explicit contract for the failing
# open — the checkpoint sidecars' degrade-to-a-verdict pattern.
_OSERROR_FAMILY = frozenset({"OSError", "IOError", "EnvironmentError",
                             "FileNotFoundError", "PermissionError",
                             "Exception", "BaseException"})
_RETRY_NAMES = frozenset({"open_with_retry", "retry_io"})


def _handles_oserror(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True
    names = []
    for n in ast.walk(t):
        if isinstance(n, ast.Name):
            names.append(n.id)
        elif isinstance(n, ast.Attribute):
            names.append(n.attr)
    return any(n in _OSERROR_FAMILY for n in names)


def _stmt_mentions_retry(stmt) -> bool:
    for n in _own_expr_nodes(stmt):
        if isinstance(n, ast.Name) and n.id in _RETRY_NAMES:
            return True
        if isinstance(n, ast.Attribute) and n.attr in _RETRY_NAMES:
            return True
    return False


def _decorated_retrying(node) -> bool:
    for dec in getattr(node, "decorator_list", []):
        for n in ast.walk(dec):
            if isinstance(n, ast.Name) and n.id == "retrying":
                return True
            if isinstance(n, ast.Attribute) and n.attr == "retrying":
                return True
    return False


def r010_unwrapped_io(proj: Project) -> List[Finding]:
    found: List[Finding] = []
    for mod in proj.by_path.values():
        p = mod.path.replace("\\", "/")
        if not p.endswith(R010_MODULE_SUFFIXES):
            continue

        def walk_stmts(stmts, protected: bool, retried: bool):
            for stmt in stmts:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    walk_stmts(stmt.body, protected,
                               retried or _decorated_retrying(stmt))
                    continue
                if isinstance(stmt, ast.ClassDef):
                    walk_stmts(stmt.body, protected, retried)
                    continue
                exempt = (protected or retried
                          or _stmt_mentions_retry(stmt))
                for n in _own_expr_nodes(stmt):
                    if (isinstance(n, ast.Call)
                            and isinstance(n.func, ast.Name)
                            and n.func.id == "open"
                            and not exempt):
                        found.append(Finding(
                            "R010", mod.path, n.lineno,
                            "raw open() on a pipeline/checkpoint hot "
                            "path bypasses utils/retry — a transient "
                            "NFS/object-store error kills the run; "
                            "use open_with_retry/retry_io, handle "
                            "OSError explicitly, or justify with a "
                            "pragma"))
                if isinstance(stmt, ast.Try):
                    prot = protected or any(_handles_oserror(h)
                                            for h in stmt.handlers)
                    walk_stmts(stmt.body, prot, retried)
                    for h in stmt.handlers:
                        walk_stmts(h.body, protected, retried)
                    walk_stmts(stmt.orelse, protected, retried)
                    walk_stmts(stmt.finalbody, protected, retried)
                    continue
                for field in _BLOCK_FIELDS:
                    sub = getattr(stmt, field, None)
                    if sub:
                        walk_stmts(sub, protected, retried)

        walk_stmts(mod.tree.body, False, False)
    return found


# --- R012: health-event catalog drift --------------------------------------

_ATTRIBUTION_SUFFIX = "fast_tffm_tpu/obs/attribution.py"
_HEALTH_SET_NAME = "HEALTH_KINDS"


def _function_scopes(tree) -> Iterable[ast.AST]:
    """Every def (and the module itself) as one scope: the emit call
    and its status-dict always share a function in this codebase
    (inline literal, or a ``fields = {...}`` built beside the call)."""
    yield tree
    for n in ast.walk(tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield n


def _scope_own_nodes(scope) -> Iterable[ast.AST]:
    """Walk one scope's own statements, not nested defs' (a nested
    def is its own scope in _function_scopes — walking it here too
    would double-report every site)."""
    body = scope.body if hasattr(scope, "body") else []
    for stmt in body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield from _walk_skip_defs(stmt)


def _health_emit_payloads(scope) -> Iterable[ast.Dict]:
    """The dict literals actually PASSED to an ``emit("health", ...)``
    call in this scope: an inline ``emit("health", {...})`` argument,
    or the scope-local ``fields = {...}`` a name argument resolves to.
    Anchoring on the argument (not every dict in the scope) keeps an
    unrelated ``{"status": "ok"}`` stats payload in the same function
    from being misread as a health kind."""
    assigns: Dict[str, List[ast.Dict]] = {}
    emits: List[ast.Call] = []
    for n in _scope_own_nodes(scope):
        if (isinstance(n, ast.Assign) and len(n.targets) == 1
                and isinstance(n.targets[0], ast.Name)
                and isinstance(n.value, ast.Dict)):
            assigns.setdefault(n.targets[0].id, []).append(n.value)
        if not (isinstance(n, ast.Call) and n.args
                and isinstance(n.args[0], ast.Constant)
                and n.args[0].value == "health"):
            continue
        base = None
        if isinstance(n.func, ast.Name):
            base = n.func.id
        elif isinstance(n.func, ast.Attribute):
            base = n.func.attr
        if base == "emit":
            emits.append(n)
    for call in emits:
        if len(call.args) < 2:
            continue
        payload = call.args[1]
        if isinstance(payload, ast.Dict):
            yield payload
        elif isinstance(payload, ast.Name):
            yield from assigns.get(payload.id, [])


def _emitted_health_kinds(proj) -> List[Tuple[str, str, int]]:
    """(kind, path, line) for every ``"status": "<kind>"`` literal in
    a dict a health-event emit actually ships."""
    out: List[Tuple[str, str, int]] = []
    for mod in proj.by_path.values():
        for scope in _function_scopes(mod.tree):
            for d in _health_emit_payloads(scope):
                for k, v in zip(d.keys, d.values):
                    if (isinstance(k, ast.Constant)
                            and k.value == "status"
                            and isinstance(v, ast.Constant)
                            and isinstance(v.value, str)):
                        out.append((v.value, mod.path, v.lineno))
    return out


def _catalog_kinds(att_mod) -> Dict[str, int]:
    """HEALTH_KINDS frozenset contents {kind: line} from
    attribution.py's AST."""
    for node in att_mod.tree.body:
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and node.targets[0].id == _HEALTH_SET_NAME
                and isinstance(node.value, ast.Call)
                and node.value.args
                and isinstance(node.value.args[0], ast.Set)):
            return {e.value: e.lineno
                    for e in node.value.args[0].elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str)}
    return {}


def r012_health_catalog(proj: Project) -> List[Finding]:
    att_mod = next((m for m in proj.by_path.values()
                    if m.path.replace("\\", "/").endswith(
                        _ATTRIBUTION_SUFFIX)), None)
    if att_mod is None:
        return []
    catalog = _catalog_kinds(att_mod)
    root = os.path.dirname(os.path.dirname(
        os.path.dirname(att_mod.path)))
    readme_path = os.path.join(root, "README.md")
    readme_text = None
    if os.path.isfile(readme_path):
        with open(readme_path, "r", encoding="utf-8") as fh:
            readme_text = fh.read()
    emitted = _emitted_health_kinds(proj)
    found: List[Finding] = []
    readme_flagged: Set[str] = set()
    for kind, path, line in emitted:
        if kind not in catalog:
            found.append(Finding(
                "R012", path, line,
                f"health kind '{kind}' is emitted here but missing "
                "from obs/attribution.HEALTH_KINDS — fmstat has no "
                "verdict/notes mapping for it; map it (and add the "
                "README catalog row) or justify with a pragma"))
        if (readme_text is not None and kind not in readme_flagged
                and not _word_in(readme_text, kind)):
            # One finding per KIND (at its first emit site), not one
            # per site: the missing artifact is the catalog row.
            readme_flagged.add(kind)
            found.append(Finding(
                "R012", path, line,
                f"health kind '{kind}' has no README health-event "
                "catalog row; document what emits it, what fmstat "
                "shows, and the first diagnostic"))
    emitted_kinds = {k for k, _, _ in emitted}
    for kind, line in sorted(catalog.items()):
        if kind not in emitted_kinds:
            found.append(Finding(
                "R012", att_mod.path, line,
                f"HEALTH_KINDS entry '{kind}' is emitted nowhere in "
                "the linted surface — a stale catalog entry (event "
                "removed?); drop it or justify with a pragma"))
    return found




# --- R014: protocol sequence divergence ------------------------------------
#
# R007 proves one shape: a collective under one arm of a RANK-conditioned
# ``if``. The protocol model (tools/fmlint/project.py, collective_ops)
# generalizes the obligation to the whole sequence: at every branch
# point in a protocol module, either both paths carry the SAME ordered
# collective-op sequence, or the condition is rank-uniform (a
# broadcast/allgather product, process_count, a constant). R014
# discharges the cases R007 cannot see: branches on per-process DATA
# (the PR 4 walk-back bug class — restore success is local until
# _all_agree), loop-carried divergence (a loop whose trip count or
# escape is not uniform), and exception arms (a handler that swallows
# an error mid-protocol leaves this rank's sequence a prefix of its
# peers').

R014_MODULE_SUFFIXES = (
    "fast_tffm_tpu/train.py", "fast_tffm_tpu/predict.py",
    "fast_tffm_tpu/checkpoint.py", "fast_tffm_tpu/data/stream.py",
    "fast_tffm_tpu/wire.py")
R014_PACKAGE_FRAGMENTS = ("fast_tffm_tpu/parallel/",)
# liveness.py IS the guard implementation: its try/except around the
# wrapped collective is the escalation path, not a protocol bug.
R014_EXCLUDE_SUFFIXES = ("fast_tffm_tpu/parallel/liveness.py",)


def _in_protocol_scope(path: str) -> bool:
    p = path.replace("\\", "/")
    if p.endswith(R014_EXCLUDE_SUFFIXES):
        return False
    return p.endswith(R014_MODULE_SUFFIXES) or any(
        frag in p for frag in R014_PACKAGE_FRAGMENTS)


def _mentions_names(expr, names: Set[str]) -> bool:
    return any(isinstance(n, ast.Name) and n.id in names
               for n in ast.walk(expr))


def _is_local_source(proj: Project, fn: FunctionInfo, expr,
                     local: Set[str] = frozenset()) -> bool:
    """A value the engine can prove is computed WITHOUT synchronizing
    AND from per-process inputs: a resolved collective-free call that
    is an instance method (``self._attempt_restore`` — instance state
    plus per-process IO) or that is fed already-local data. A plain
    function over config/constants stays neutral — the config file is
    identical on every rank by the deployment contract, so
    ``is_stream_source(cfg.train_files)`` is uniform, while unresolved
    calls stay neutral by the underclaim policy. Any collective en
    route makes the value uniform (_is_sanitizing wins before this is
    consulted)."""
    for n in ast.walk(expr):
        if not isinstance(n, ast.Call):
            continue
        callee = resolve_call(proj, fn, n.func)
        if callee is None or proj.collectives_of(callee):
            continue
        if isinstance(n.func, ast.Attribute):
            parts = _dotted(n.func)
            if parts and parts[0] in ("self", "cls"):
                return True
        for arg in list(n.args) + [kw.value for kw in n.keywords]:
            for a in ast.walk(arg):
                if isinstance(a, ast.Name) and (a.id in local
                                                or a.id == "self"):
                    return True
    return False


_TAINT_TIMELINES: "weakref.WeakKeyDictionary[Project, Dict[str, list]]" \
    = weakref.WeakKeyDictionary()


def _local_taint_at(proj: Project, fn: FunctionInfo,
                    line: int) -> Set[str]:
    """Names holding provably-local (per-process) values at ``line``,
    from the function's taint timeline (computed once per function:
    R014 queries every branch point, and replaying the resolve-heavy
    event scan per query dominated the whole sweep's wall time)."""
    snap: Set[str] = set()
    for lineno, names in _taint_timeline(proj, fn):
        if lineno >= line:
            break
        snap = names
    return snap


def _taint_timeline(proj: Project, fn: FunctionInfo):
    """[(lineno, local-name snapshot AFTER that line's event)] by the
    same linear source-order replay as R007's rank taint: local-source
    assignments taint (tuple unpacks taint every element — the
    ``restored, err = self._attempt_restore(...)`` shape),
    collective-routed assignments sanitize, exception captures and
    handler-body assignments are local by nature (an error outcome is
    per-process)."""
    per_fn = _TAINT_TIMELINES.setdefault(proj, {})
    cached = per_fn.get(fn.qualname)
    if cached is not None:
        return cached
    events: List[Tuple[int, Optional[ast.AST], List[str], bool]] = []
    for n in _walk_skip_defs(fn.node):
        if isinstance(n, ast.Assign) and len(n.targets) == 1:
            t = n.targets[0]
            names = [e.id for e in (t.elts if isinstance(t, ast.Tuple)
                                    else [t])
                     if isinstance(e, ast.Name)]
            if names:
                events.append((n.lineno, n.value, names, False))
    for n in _walk_skip_defs(fn.node):
        if isinstance(n, ast.Try):
            for h in n.handlers:
                if h.name:
                    events.append((h.lineno, None, [h.name], True))
                for hn in h.body:
                    for a in _walk_skip_defs(hn):
                        if isinstance(a, ast.Assign):
                            names = [e.id for t in a.targets
                                     for e in (t.elts if isinstance(
                                         t, ast.Tuple) else [t])
                                     if isinstance(e, ast.Name)]
                            if names:
                                events.append((a.lineno, a.value,
                                               names, True))
    timeline: List[Tuple[int, Set[str]]] = []
    local: Set[str] = set()
    for lineno, value, names, forced in sorted(
            events, key=lambda e: e[0]):
        if value is not None and _is_sanitizing(proj, fn, value):
            local.difference_update(names)
        elif forced or (value is not None and (
                _is_local_source(proj, fn, value, local)
                or _mentions_names(value, local))):
            local.update(names)
        timeline.append((lineno, set(local)))
    per_fn[fn.qualname] = timeline
    return timeline


def _condition_class(proj: Project, fn: FunctionInfo, test,
                     line: int) -> str:
    """'uniform' (broadcast-produced — safe to branch on), 'rank'
    (R007's domain), 'local' (per-process data), or 'neutral'
    (parameters, unresolved calls — not provably anything)."""
    if _is_sanitizing(proj, fn, test):
        return "uniform"
    if _mentions_rank(test, _tainted_at(proj, fn, _taint_assigns(fn),
                                        line)):
        return "rank"
    local = _local_taint_at(proj, fn, line)
    if (_mentions_names(test, local)
            or _is_local_source(proj, fn, test, local)):
        return "local"
    return "neutral"


def _raise_terminated(stmts: Sequence[ast.stmt]) -> bool:
    return bool(stmts) and isinstance(stmts[-1], ast.Raise)


def _first_mismatch(a: Sequence[str], b: Sequence[str]
                    ) -> Tuple[str, str]:
    for x, y in zip(a, b):
        if x != y:
            return x, y
    return ((a[len(b)], "<nothing>") if len(a) > len(b)
            else ("<nothing>", b[len(a)]))


def _handler_escalates(stmts: Sequence[ast.stmt]) -> bool:
    """A handler whose last statement re-raises (or hard-exits) keeps
    the failure loud: the guard layer converts it to a diagnosed,
    bounded death instead of a silently shorter protocol sequence."""
    if not stmts:
        return False
    last = stmts[-1]
    if isinstance(last, ast.Raise):
        return True
    if isinstance(last, ast.Expr) and isinstance(last.value, ast.Call):
        base = None
        if isinstance(last.value.func, ast.Name):
            base = last.value.func.id
        elif isinstance(last.value.func, ast.Attribute):
            base = last.value.func.attr
        return base in ("exit", "_exit", "abort")
    return False


def _loop_escape_ifs(loop) -> Iterable[ast.If]:
    """``if`` statements anywhere in the loop's own body containing a
    break/return that escapes THIS loop (breaks inside nested loops
    belong to those loops and are checked there)."""
    def scan(stmts, innermost: bool):
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            if isinstance(stmt, (ast.While, ast.For)):
                yield from scan(stmt.body, False)
                yield from scan(stmt.orelse, False)
                continue
            if isinstance(stmt, ast.If):
                # Break/Continue inside a NESTED loop bind to it; the
                # arm walk below rebinds across loop boundaries.
                if _arm_escapes(stmt, innermost):
                    yield stmt
                yield from scan(stmt.body, innermost)
                yield from scan(stmt.orelse, innermost)
                continue
            for field in _BLOCK_FIELDS:
                sub = getattr(stmt, field, None)
                if sub:
                    yield from scan(sub, innermost)
            for h in getattr(stmt, "handlers", []) or []:
                yield from scan(h.body, innermost)
    yield from scan(loop.body, True)


def _arm_escapes(stmt: ast.If, innermost: bool) -> bool:
    def block_escapes(stmts) -> bool:
        for s in stmts:
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                continue
            if isinstance(s, ast.Return):
                return True
            if innermost and isinstance(s, (ast.Break, ast.Continue)):
                return True
            if isinstance(s, (ast.While, ast.For)):
                # returns still escape; break/continue rebind
                if any(isinstance(n, ast.Return)
                       for n in _walk_skip_defs(s)):
                    return True
                continue
            for field in _BLOCK_FIELDS:
                sub = getattr(s, field, None)
                if sub and block_escapes(sub):
                    return True
            for h in getattr(s, "handlers", []) or []:
                if block_escapes(h.body):
                    return True
        return False
    return block_escapes(stmt.body) or block_escapes(stmt.orelse)


def r014_protocol_divergence(proj: Project) -> List[Finding]:
    found: List[Finding] = []
    for fn in sorted(proj.functions.values(),
                     key=lambda f: (f.module.path, f.node.lineno)):
        if not _in_protocol_scope(fn.module.path):
            continue
        flagged: Set[int] = set()

        def flag(line: int, message: str) -> None:
            if line not in flagged:
                flagged.add(line)
                found.append(Finding("R014", fn.module.path, line,
                                     message))

        short = fn.qualname.rsplit(".", 1)[-1]
        # (a) branch divergence on per-process data
        for block in _statement_blocks(fn.node):
            for i, stmt in enumerate(block):
                if not isinstance(stmt, ast.If):
                    continue
                disp = _condition_class(proj, fn, stmt.test,
                                        stmt.lineno)
                if disp != "local":
                    continue
                # A raise-terminated arm with no collectives of its
                # own is the sanctioned die-loudly path: the raising
                # rank's death goes stale on the lease table and the
                # peers' parked collective becomes a diagnosed,
                # bounded WorkerLostError exit — divergence-by-dying
                # is how per-process failures are DESIGNED to surface
                # when no walk-back recovery exists.
                if any(_raise_terminated(arm)
                       and not collective_ops(proj, fn, arm)
                       for arm in (stmt.body, stmt.orelse)):
                    continue
                arm_t: List[ast.stmt] = list(stmt.body)
                arm_f: List[ast.stmt] = list(stmt.orelse)
                tail = list(block[i + 1:])
                if _terminates(arm_t) and not _terminates(arm_f):
                    arm_f = arm_f + tail
                elif _terminates(arm_f) and not _terminates(arm_t):
                    arm_t = arm_t + tail
                seq_t = collective_ops(proj, fn, arm_t)
                seq_f = collective_ops(proj, fn, arm_f)
                if seq_t == seq_f:
                    continue
                a, b = _first_mismatch(seq_t, seq_f)
                flag(stmt.lineno,
                     "collective protocol diverges on per-process "
                     f"data (in {short}): the branch condition is a "
                     "local value no collective agreed on, and the "
                     f"arms' collective sequences differ ({a} vs {b}) "
                     "— ranks whose data differs pair mismatched "
                     "collectives and deadlock; agree on the "
                     "condition first (the _all_agree/_broadcast_int "
                     "pattern) or justify with a pragma")
        # (b) loop-carried divergence
        for loop in _walk_skip_defs(fn.node):
            if not isinstance(loop, (ast.While, ast.For)):
                continue
            body_ops = collective_ops(proj, fn, loop.body)
            if not body_ops:
                continue
            ctrl = loop.test if isinstance(loop, ast.While) \
                else loop.iter
            disp = _condition_class(proj, fn, ctrl, loop.lineno)
            if disp in ("rank", "local"):
                flag(loop.lineno,
                     f"collective(s) {', '.join(sorted(set(body_ops)))}"
                     " execute inside a loop whose "
                     f"{'condition' if isinstance(loop, ast.While) else 'iterable'}"
                     f" is {disp} (per-process) — ranks run different "
                     f"iteration counts (in {short}) and the extra "
                     "iterations' collectives never match; drive the "
                     "loop off a broadcast/allgather-agreed bound or "
                     "justify with a pragma")
            for esc in _loop_escape_ifs(loop):
                disp = _condition_class(proj, fn, esc.test, esc.lineno)
                if disp not in ("rank", "local"):
                    continue
                # An escape whose arm-set difference R007 already
                # reports (rank case) stays R007's finding.
                if disp == "rank":
                    kt = _arm_collectives(proj, fn, esc.body)
                    kf = _arm_collectives(proj, fn, esc.orelse)
                    if kt != kf:
                        continue
                flag(esc.lineno,
                     f"a {disp} (per-process) condition escapes a "
                     f"collective-bearing loop early (in {short}): "
                     "ranks leave the loop on different iterations "
                     f"and the remaining {', '.join(sorted(set(body_ops)))}"
                     " calls go unmatched; make the escape decision "
                     "a broadcast/allgather product or justify with "
                     "a pragma")
        # (c) exception-arm divergence
        for t in _walk_skip_defs(fn.node):
            if not isinstance(t, ast.Try):
                continue
            try_ops = collective_ops(proj, fn, t.body)
            if not try_ops:
                continue
            for h in t.handlers:
                if _handler_escalates(h.body):
                    continue
                flag(h.lineno,
                     "this handler swallows a failure of a "
                     "collective-bearing try body (ops: "
                     f"{', '.join(try_ops)}) in {short}: the "
                     "excepting rank continues with a shorter "
                     "collective sequence than its peers and the "
                     "cluster deadlocks at the next sync point; "
                     "re-raise (the liveness guard converts it to a "
                     "diagnosed bounded exit) or justify with a "
                     "pragma")
    return found


# --- R015: collective reachable from a spawned thread ----------------------

def r015_threaded_collective(proj: Project) -> List[Finding]:
    """A blocking collective posted from a helper thread: the peers'
    protocol order assumes collectives post from the driver loop, the
    deadline guard's in-flight slot is process-global (a thread's
    collective shadows the driver's), and two threads posting
    concurrently interleave nondeterministically across ranks —
    ROADMAP item 2's overlap work steps exactly here."""
    found: List[Finding] = []
    for q in sorted(proj.thread_funcs):
        fn = proj.functions.get(q)
        if fn is None:
            continue
        for line, kind in sorted(fn.collective_sites):
            found.append(Finding(
                "R015", fn.module.path, line,
                f"blocking collective {kind} can execute on a spawned "
                f"thread ({fn.qualname.rsplit('.', 1)[-1]} is "
                "thread-reachable per the Thread-target summary): "
                "collective order across ranks is only defined for "
                "the driver loop — post it from the main thread, or "
                "justify a provably-serialized design with a pragma"))
    return found


# --- R016: lock-order cycles -----------------------------------------------

def _lock_edges(proj: Project) -> Dict[Tuple[str, str],
                                       Tuple[str, int, str]]:
    """Directed held->acquired edges with one witness site each:
    lexical nesting (``with a: with b:``) and interprocedural
    acquisition (a call made under ``a`` into a function that may
    acquire ``b``)."""
    edges: Dict[Tuple[str, str], Tuple[str, int, str]] = {}
    for fn in sorted(proj.functions.values(),
                     key=lambda f: (f.module.path, f.node.lineno)):
        short = fn.qualname.rsplit(".", 1)[-1]
        for acq in fn.lock_acquires:
            for h in acq.held:
                if h != acq.lock:
                    edges.setdefault((h, acq.lock), (
                        fn.module.path, acq.line,
                        f"{short}() takes {acq.lock} while holding "
                        f"{h}"))
        for lc in fn.locked_calls:
            if lc.callee is None:
                continue
            for m in sorted(proj.may_locks.get(lc.callee, ())):
                for h in lc.locks:
                    if m != h:
                        edges.setdefault((h, m), (
                            fn.module.path, lc.line,
                            f"{short}() calls "
                            f"{lc.callee.rsplit('.', 1)[-1]}() "
                            f"(which takes {m}) while holding {h}"))
    return edges


def _sccs(nodes: Set[str],
          succ: Dict[str, Set[str]]) -> List[List[str]]:
    """Tarjan, iterative; returns SCCs with >= 2 nodes (sorted)."""
    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    on_stack: Set[str] = set()
    stack: List[str] = []
    out: List[List[str]] = []
    counter = [0]

    def strongconnect(v: str) -> None:
        work = [(v, iter(sorted(succ.get(v, ()))))]
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on_stack.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on_stack.add(w)
                    work.append((w, iter(sorted(succ.get(w, ())))))
                    advanced = True
                    break
                if w in on_stack:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                comp = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    comp.append(w)
                    if w == node:
                        break
                if len(comp) >= 2:
                    out.append(sorted(comp))

    for v in sorted(nodes):
        if v not in index:
            strongconnect(v)
    return out


def r016_lock_order_cycle(proj: Project) -> List[Finding]:
    edges = _lock_edges(proj)
    succ: Dict[str, Set[str]] = {}
    nodes: Set[str] = set()
    for a, b in edges:
        succ.setdefault(a, set()).add(b)
        nodes.add(a)
        nodes.add(b)
    found: List[Finding] = []
    for comp in _sccs(nodes, succ):
        in_cycle = sorted((a, b) for (a, b) in edges
                          if a in comp and b in comp)
        witness = [f"{edges[e][2]} [{os.path.basename(edges[e][0])}:"
                   f"{edges[e][1]}]" for e in in_cycle]
        path, line, _ = edges[in_cycle[0]]
        found.append(Finding(
            "R016", path, line,
            "lock-order cycle between "
            f"{' and '.join(comp)}: {'; '.join(witness)} — two "
            "threads taking these locks in opposite orders deadlock; "
            "pick one global order (document it at the lock "
            "definitions) or justify with a pragma"))
    return found


# --- R017: lock held across a collective / blocking fetch ------------------

def r017_lock_across_blocking(proj: Project) -> List[Finding]:
    found: List[Finding] = []
    for fn in sorted(proj.functions.values(),
                     key=lambda f: (f.module.path, f.node.lineno)):
        short = fn.qualname.rsplit(".", 1)[-1]
        seen_lines: Set[int] = set()
        for lc in fn.locked_calls:
            ops: List[str] = []
            if lc.basename in COLLECTIVE_NAMES:
                ops.append(lc.basename)
            if lc.basename in FETCH_NAMES:
                ops.append(lc.basename)
            if lc.callee is not None:
                ops.extend(sorted(proj.collectives_of(lc.callee)))
                if lc.callee in proj.may_fetch:
                    ops.append(
                        f"{lc.callee.rsplit('.', 1)[-1]}() "
                        "(reaches a device fetch)")
            if not ops or lc.line in seen_lines:
                continue
            seen_lines.add(lc.line)
            found.append(Finding(
                "R017", fn.module.path, lc.line,
                f"{' + '.join(dict.fromkeys(ops))} runs while "
                f"{short}() holds {lc.locks[-1]}: a blocked "
                "collective/fetch (dead peer, slow device) wedges "
                "every thread contending for the lock — and if the "
                "unblocking path needs it, the process deadlocks "
                "outright; move the blocking call outside the lock "
                "(snapshot under the lock, block after) or justify "
                "with a pragma"))
    return found


# Catalog-drift rules reason about ABSENCE over the whole surface
# ("this knob/kind is emitted/used nowhere") — meaningless on the
# --changed subset, where the emitting module may simply not be in
# the closure. run_paths(partial=True) skips them.
r009_config_drift.needs_full_surface = True
r012_health_catalog.needs_full_surface = True

PROGRAM_RULES = (r007_divergent_collective,
                 r008_unsynchronized_shared_mutation,
                 r009_config_drift,
                 r010_unwrapped_io,
                 r012_health_catalog,
                 r014_protocol_divergence,
                 r015_threaded_collective,
                 r016_lock_order_cycle,
                 r017_lock_across_blocking)
