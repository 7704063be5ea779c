"""fmlint core: findings, suppression pragmas, runner, CLI.

Rules are stdlib-``ast`` analyses (tools/fmlint/rules.py) run per
file; findings then filter through the suppression pragmas:

    x = float(loss)   # fmlint: disable=R001 -- probed link, live mode
    # fmlint: disable=R001 -- host allgather result, not a device array
    spilled = int(tot[:, 0].sum())
    # fmlint: disable-file=R002 -- CLI module, print IS the output

``disable=`` on a code line suppresses matching findings on that line;
as a whole-line comment it suppresses the entire NEXT statement
(multi-line calls included). ``disable-file=`` suppresses the rule for
the whole file. The text after ``--`` is the REQUIRED justification —
a pragma without one is itself a finding (R000).
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


_PRAGMA = re.compile(
    r"#\s*fmlint:\s*(disable|disable-file)=([A-Z0-9,]+)"
    r"(?:\s*--\s*(.*))?")


@dataclasses.dataclass
class Suppressions:
    # rule -> set of suppressed line numbers (resolved statement spans)
    lines: Dict[str, Set[int]]
    file_rules: Set[str]
    bad_pragmas: List[Finding]  # R000: pragma without justification

    def allows(self, f: Finding) -> bool:
        if f.rule in self.file_rules:
            return True
        return f.line in self.lines.get(f.rule, ())


def _statement_spans(tree: ast.AST) -> List[Tuple[int, int]]:
    """(lineno, end_lineno) for every statement, sorted by start."""
    spans = []
    for node in ast.walk(tree):
        if isinstance(node, ast.stmt):
            spans.append((node.lineno, node.end_lineno or node.lineno))
    return sorted(spans)


def parse_suppressions(path: str, source: str,
                       tree: ast.AST) -> Suppressions:
    lines: Dict[str, Set[int]] = {}
    file_rules: Set[str] = set()
    bad: List[Finding] = []
    spans = _statement_spans(tree)

    def next_stmt_span(after_line: int) -> Tuple[int, int]:
        for lo, hi in spans:
            if lo > after_line:
                return lo, hi
        return after_line + 1, after_line + 1

    for i, text in enumerate(source.splitlines(), start=1):
        m = _PRAGMA.search(text)
        if not m:
            continue
        kind, rules_s, why = m.groups()
        rules = [r for r in rules_s.split(",") if r]
        if not (why or "").strip():
            bad.append(Finding(
                "R000", path, i,
                "suppression pragma without a `-- justification`"))
            continue
        if kind == "disable-file":
            file_rules.update(rules)
            continue
        whole_line = text.lstrip().startswith("#")
        if whole_line:
            lo, hi = next_stmt_span(i)
            covered = range(lo, hi + 1)
        else:
            covered = (i,)
        for r in rules:
            lines.setdefault(r, set()).update(covered)
    return Suppressions(lines=lines, file_rules=file_rules,
                       bad_pragmas=bad)


# --- parse cache -----------------------------------------------------------
#
# Parsing + suppression-scanning ~80 modules dominates a no-finding
# sweep's cost. Each file's (source, tree, suppressions) triple is
# pickled under .fmlint_cache/ keyed by (mtime_ns, size): an unchanged
# file is unpickled instead of re-parsed. Bump _CACHE_VERSION when the
# cached shape changes (pragma grammar, Suppressions layout). A cache
# that can't be read or written is ignored — caching is an
# optimization, never a correctness dependency.

_CACHE_VERSION = 1


def _cache_key(path: str) -> Optional[tuple]:
    try:
        st = os.stat(path)
    except OSError:
        return None
    return (_CACHE_VERSION, sys.version_info[:2], st.st_mtime_ns,
            st.st_size)


def _cache_file(cache_dir: str, path: str) -> str:
    import hashlib
    return os.path.join(
        cache_dir, hashlib.sha1(path.encode("utf-8")).hexdigest()
        + ".pkl")


def _cache_get(cache_dir: str, path: str):
    import pickle
    key = _cache_key(path)
    if key is None:
        return None
    try:
        with open(_cache_file(cache_dir, path), "rb") as fh:
            entry = pickle.load(fh)
        if entry.get("key") == key:
            return entry["value"]
    except Exception:
        pass
    return None


def _cache_put(cache_dir: str, path: str, value) -> None:
    import pickle
    key = _cache_key(path)
    if key is None:
        return
    try:
        os.makedirs(cache_dir, exist_ok=True)
        target = _cache_file(cache_dir, path)
        tmp = target + f".tmp{os.getpid()}"
        with open(tmp, "wb") as fh:
            pickle.dump({"key": key, "value": value}, fh,
                        protocol=pickle.HIGHEST_PROTOCOL)
        os.replace(tmp, target)  # atomic: no torn cache entries
    except Exception:
        pass


def default_cache_dir() -> str:
    return os.path.join(repo_root(), ".fmlint_cache")


def _parse_one(path: str, source: Optional[str] = None,
               cache_dir: Optional[str] = None):
    """(source, tree, suppressions) for one file, or a one-element
    R999 finding list when it doesn't parse. ``source`` (the overlay
    seam) bypasses the cache entirely."""
    if source is None and cache_dir is not None:
        hit = _cache_get(cache_dir, path)
        if hit is not None:
            return hit
    from_disk = source is None
    if source is None:
        with open(path, "r", encoding="utf-8") as fh:
            source = fh.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return source, None, [Finding("R999", path, e.lineno or 0,
                                      f"syntax error: {e.msg}")]
    result = source, tree, parse_suppressions(path, source, tree)
    if from_disk and cache_dir is not None:
        _cache_put(cache_dir, path, result)
    return result


def run_file(path: str) -> List[Finding]:
    """Per-file rules only (R000-R006 + R999). The whole-program pass
    (R007-R017; tools/fmlint/xrules.py) needs the full surface — use
    ``run_paths``."""
    from tools.fmlint.rules import RULES
    source, tree, supp = _parse_one(path)
    if tree is None:
        return supp  # the R999 finding list
    found: List[Finding] = list(supp.bad_pragmas)
    for rule_fn in RULES:
        found.extend(f for f in rule_fn(path, tree)
                     if not supp.allows(f))
    return sorted(found, key=lambda f: (f.path, f.line, f.rule))


def collect_files(paths: Sequence[str]) -> List[str]:
    """Expand dirs to their .py files. A path that doesn't exist or
    isn't lintable raises — a typo'd lint target must fail the gate,
    not exit 0 having linted zero files. Fully deterministic: both the
    directory descent order and the per-directory file order are
    sorted, so finding order — and therefore baseline diffs — is
    stable across filesystems."""
    out: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for root, _dirs, names in os.walk(p):
                # In-place: os.walk descends in THIS order.
                _dirs[:] = sorted(d for d in _dirs
                                  if d != "__pycache__")
                out.extend(os.path.join(root, n) for n in sorted(names)
                           if n.endswith(".py"))
        elif os.path.isfile(p) and p.endswith(".py"):
            out.append(p)
        else:
            raise FileNotFoundError(
                f"fmlint: {p!r} is not a directory or .py file")
    return out


def run_paths(paths: Sequence[str],
              overlay: Optional[Dict[str, str]] = None,
              baseline: Optional[str] = None,
              cache_dir: Optional[str] = None,
              profile: Optional[Dict[str, float]] = None,
              partial: bool = False) -> List[Finding]:
    """The whole-program pass: every file parsed ONCE, per-file rules
    (R000-R006) plus the cross-file rules (R007-R017) over one shared
    project model (tools/fmlint/project.py). ``overlay`` maps absolute
    paths to replacement source (the mutant-testing seam);
    ``baseline`` filters findings recorded in a committed baseline
    file (gradual adoption — see load_baseline); ``cache_dir`` reuses
    pickled parses for unchanged files (the CLI passes
    .fmlint_cache/); ``profile``, when a dict, receives per-stage and
    per-rule wall seconds; ``partial`` marks a subset surface
    (--changed): rules whose contract is "X appears NOWHERE on the
    surface" (the R009/R012 stale/drift directions) are skipped —
    absence over a subset proves nothing, and the full sweep remains
    the gate."""
    import time as _time
    from tools.fmlint.rules import RULES
    from tools.fmlint.project import load_project
    from tools.fmlint.xrules import PROGRAM_RULES

    def clocked(name: str, fn, *a):
        t0 = _time.perf_counter()
        out = fn(*a)
        if profile is not None:
            profile[name] = profile.get(name, 0.0) \
                + _time.perf_counter() - t0
        return out

    overlay = {os.path.abspath(k): v for k, v in (overlay or {}).items()}
    found: List[Finding] = []
    entries = []                      # (abspath, source, tree)
    supp_by_path: Dict[str, Suppressions] = {}
    for f in collect_files(paths):
        ap = os.path.abspath(f)
        source, tree, supp = clocked(
            "parse", _parse_one, ap, overlay.get(ap), cache_dir)
        if tree is None:
            found.extend(supp)        # R999: excluded from the project
            continue
        entries.append((ap, source, tree))
        supp_by_path[ap] = supp
        found.extend(supp.bad_pragmas)
        for rule_fn in RULES:
            found.extend(x for x in clocked(rule_fn.__name__,
                                            rule_fn, ap, tree)
                         if not supp.allows(x))
    proj = clocked("load_project", load_project, entries)
    for rule_fn in PROGRAM_RULES:
        if partial and getattr(rule_fn, "needs_full_surface", False):
            continue
        for x in clocked(rule_fn.__name__, rule_fn, proj):
            supp = supp_by_path.get(os.path.abspath(x.path))
            # Non-python findings (sample.cfg drift) carry no pragma
            # surface; the baseline below is their suppression path.
            if supp is None or not supp.allows(x):
                found.append(x)
    if baseline:
        found = apply_baseline(found, baseline, proj.root)
    return sorted(found, key=lambda f: (f.path, f.line, f.rule))


# --- incremental mode (--changed) ------------------------------------------

def _git_dirty_files(root: str) -> List[str]:
    """Absolute paths of git-dirty (modified/added/renamed/untracked)
    .py files under ``root``; [] when git is unavailable."""
    import subprocess
    try:
        out = subprocess.run(
            ["git", "-C", root, "status", "--porcelain"],
            capture_output=True, text=True, timeout=30, check=True
        ).stdout
    except Exception:
        return []
    dirty: List[str] = []
    for line in out.splitlines():
        if len(line) < 4:
            continue
        rel = line[3:]
        if " -> " in rel:             # rename: lint the new name
            rel = rel.split(" -> ", 1)[1]
        rel = rel.strip().strip('"')
        if rel.endswith(".py"):
            dirty.append(os.path.join(root, rel))
    return dirty


def _imported_names(tree: ast.AST, modname: str) -> Set[str]:
    """Dotted module names this tree imports (absolute form),
    relative imports resolved against ``modname``."""
    out: Set[str] = set()
    pkg_parts = modname.split(".")
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = pkg_parts[:len(pkg_parts) - node.level]
                stem = ".".join(base + ([node.module]
                                        if node.module else []))
            else:
                stem = node.module or ""
            if stem:
                out.add(stem)
                # `from pkg import name` may bind the submodule
                out.update(f"{stem}.{alias.name}"
                           for alias in node.names)
    return out


def changed_closure(paths: Sequence[str],
                    cache_dir: Optional[str] = None) -> List[str]:
    """The git-dirty .py files of the surface plus their reverse-
    import closure (everything that imports them, transitively) — the
    files whose findings an edit can change. Program rules then run
    over this subset only: the fast inner-loop check; the full sweep
    remains the gate."""
    from tools.fmlint.project import package_root
    files = [os.path.abspath(f) for f in collect_files(paths)]
    if not files:
        return []
    root = package_root(os.path.commonpath(
        [os.path.dirname(f) for f in files]))
    dirty = {f for f in _git_dirty_files(repo_root()) if f in set(files)}
    if not dirty:
        return []

    def modname(ap: str) -> str:
        rel = os.path.relpath(ap, root)
        return rel[:-3].replace(os.sep, ".")

    by_mod = {modname(f): f for f in files}
    importers: Dict[str, Set[str]] = {}   # file -> files importing it
    for f in files:
        parsed = _parse_one(f, cache_dir=cache_dir)
        tree = parsed[1]
        if tree is None:
            continue
        for name in _imported_names(tree, modname(f)):
            target = by_mod.get(name)
            if target is not None and target != f:
                importers.setdefault(target, set()).add(f)
    closure = set(dirty)
    frontier = list(dirty)
    while frontier:
        for dep in importers.get(frontier.pop(), ()):
            if dep not in closure:
                closure.add(dep)
                frontier.append(dep)
    return sorted(closure)


# --- committed baseline ----------------------------------------------------
#
# Gradual adoption: a repo turning a new rule on records its existing
# findings once (``--update-baseline``) and commits the file; the gate
# then fails only on NEW findings. Entries are line-number-free
# (``relpath|rule|message``) so unrelated edits shifting a file don't
# churn the baseline; each entry absorbs at most as many findings as
# its multiplicity.

def load_baseline(path: str) -> List[str]:
    keys: List[str] = []
    with open(path, "r", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if line and not line.startswith("#"):
                keys.append(line)
    return keys


def baseline_key(f: Finding, root: str) -> str:
    rel = os.path.relpath(os.path.abspath(f.path), root)
    return f"{rel.replace(os.sep, '/')}|{f.rule}|{f.message}"


def apply_baseline(findings: List[Finding], path: str,
                   root: str) -> List[Finding]:
    from collections import Counter
    budget = Counter(load_baseline(path))
    out: List[Finding] = []
    for f in findings:
        k = baseline_key(f, root)
        if budget.get(k, 0) > 0:
            budget[k] -= 1
        else:
            out.append(f)
    return out


def write_baseline(findings: List[Finding], path: str,
                   root: str) -> None:
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("# fmlint baseline — one `relpath|rule|message` per "
                 "accepted pre-existing finding.\n"
                 "# Regenerate with: python -m tools.fmlint "
                 "--update-baseline\n")
        for f in findings:
            fh.write(baseline_key(f, root) + "\n")


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def project_root_for(paths: Sequence[str]) -> str:
    """The root baseline keys are computed against — the same
    common-directory derivation the project loader uses, so a baseline
    written by ``--update-baseline`` matches what ``run_paths``
    applies."""
    from tools.fmlint.project import package_root
    dirs = [os.path.dirname(os.path.abspath(f))
            for f in collect_files(paths)]
    return package_root(os.path.commonpath(dirs)) if dirs \
        else os.getcwd()


def default_paths() -> List[str]:
    """The repo's lint surface when run with no arguments: the package,
    the tools, and the CLI entry points (each rule scopes itself to the
    modules it governs; the whole surface gets the R999 parse gate and
    the cross-file rules)."""
    here = repo_root()
    return [os.path.join(here, "fast_tffm_tpu"),
            os.path.join(here, "tools"),
            os.path.join(here, "run_tffm.py")]


def default_baseline_path() -> Optional[str]:
    p = os.path.join(repo_root(), "tools", "fmlint", "baseline.txt")
    return p if os.path.isfile(p) else None


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    as_json = update = changed = do_profile = False
    json_out = protocol = None
    baseline = default_baseline_path()
    cache_dir: Optional[str] = default_cache_dir()
    paths: List[str] = []
    i = 0
    while i < len(args):
        a = args[i]
        if a == "--json":
            as_json = True
        elif a == "--update-baseline":
            update = True
        elif a == "--no-baseline":
            baseline = None
        elif a == "--no-cache":
            cache_dir = None
        elif a == "--changed":
            changed = True
        elif a == "--profile":
            do_profile = True
        elif a in ("--baseline", "--json-out", "--protocol"):
            flag = a
            i += 1
            if i >= len(args):
                print(f"fmlint: {flag} needs a value", file=sys.stderr)
                return 2
            if flag == "--baseline":
                baseline = args[i]
            elif flag == "--json-out":
                json_out = args[i]
            else:
                protocol = args[i]
        else:
            paths.append(a)
        i += 1
    if protocol is not None:
        # Dump the protocol automaton for one driver entry point
        # (qualified name, e.g. fast_tffm_tpu.train._train_session).
        from tools.fmlint.project import (load_project,
                                          protocol_automaton)
        entries = []
        for f in collect_files(paths or default_paths()):
            ap = os.path.abspath(f)
            source, tree, _supp = _parse_one(ap, cache_dir=cache_dir)
            if tree is not None:
                entries.append((ap, source, tree))
        proj = load_project(entries)
        if protocol not in proj.functions:
            close = sorted(q for q in proj.functions
                           if q.endswith("." + protocol)
                           or protocol in q)[:8]
            print(f"fmlint: unknown function {protocol!r}"
                  + (f"; close matches: {', '.join(close)}"
                     if close else ""), file=sys.stderr)
            return 2
        for line in protocol_automaton(proj, protocol):
            print(line)
        return 0
    lint_paths = paths or default_paths()
    if changed:
        lint_paths = changed_closure(lint_paths, cache_dir=cache_dir)
        if not lint_paths:
            print("fmlint: no git-dirty files on the lint surface",
                  file=sys.stderr)
            return 0
        print(f"fmlint: --changed linting {len(lint_paths)} file(s) "
              "(catalog-drift rules deferred to the full sweep)",
              file=sys.stderr)
    prof: Optional[Dict[str, float]] = {} if do_profile else None
    try:
        findings = run_paths(lint_paths,
                             baseline=None if update else baseline,
                             cache_dir=cache_dir, profile=prof,
                             partial=changed)
    except FileNotFoundError as e:
        print(e, file=sys.stderr)
        return 2
    if prof is not None:
        total = sum(prof.values())
        print("fmlint: per-stage/per-rule wall time:", file=sys.stderr)
        for name, secs in sorted(prof.items(), key=lambda kv: -kv[1]):
            print(f"  {secs * 1000:8.1f} ms  {name}", file=sys.stderr)
        print(f"  {total * 1000:8.1f} ms  total", file=sys.stderr)
    if update:
        target = baseline or os.path.join(repo_root(), "tools",
                                          "fmlint", "baseline.txt")
        write_baseline(findings, target,
                       project_root_for(paths or default_paths()))
        print(f"fmlint: wrote {len(findings)} baseline entr"
              f"{'y' if len(findings) == 1 else 'ies'} to {target}",
              file=sys.stderr)
        return 0
    if as_json or json_out is not None:
        import json
        payload = json.dumps({
            "findings": [dataclasses.asdict(f) for f in findings],
            "count": len(findings)}, indent=2)
        if json_out is not None:
            # CI artifact: machine-readable findings alongside the
            # human rendering (make lint publishes this).
            with open(json_out, "w", encoding="utf-8") as fh:
                fh.write(payload + "\n")
        if as_json:
            print(payload)
    if not as_json:
        for f in findings:
            print(f.render())
    if findings:
        print(f"fmlint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    return 0
