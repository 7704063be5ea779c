"""fmlint whole-program layer: the project loader the cross-file rules
(tools/fmlint/xrules.py, R007-R012 and the R014-R017 protocol/lock
model checker) consume.

Every module on the lint surface is parsed ONCE into a ``Project``:

- an import table per module (``import a.b as c`` / ``from a import b``
  in any scope — function-level imports, which this codebase uses
  heavily to defer jax, are treated module-wide);
- a function index over plain functions, methods, and nested defs
  (``pkg.mod.Class.method``, ``pkg.mod.outer.worker``);
- a call graph restricted to what static resolution can PROVE:
  bare names through local/nested/module scope and imports,
  ``self.method()`` within the enclosing class,
  ``param.method()`` where the parameter (this function's or an
  enclosing one's) is annotated with a class of the same module
  (``loop: StepLoop``), and ``imported_module.func()`` chains.
  Attribute calls on arbitrary objects stay unresolved — the
  summaries underclaim rather than guess, so rule findings are
  evidence, not speculation;
- fixpoint summaries over that graph:

  * ``may_collectives[qualname]`` — which blocking collectives
    (``process_allgather``, ``broadcast_one_to_all``,
    ``sync_global_devices``, ``guarded_collective``) a call to this
    function may transitively execute (R007's reachability);
  * ``thread_funcs`` — functions that can run on a spawned thread:
    every resolved ``threading.Thread(target=...)`` entry point plus
    its transitive callees (R008's "proves can run on a thread");
  * per-function shared-state writes (``self.attr`` assignment /
    augassign / subscript store, known in-place mutator calls, and
    mutations of module-level globals) with a held-a-lock bit
    (R008's evidence);
  * project-wide ``FM_*`` environment reads and ``cfg.<knob>``
    attribute reads (R009's env/knob consistency).

Loading accepts a source ``overlay`` keyed by absolute path, so tests
can analyze the REAL repo with one file's source swapped for a mutant
(the R007 seeded-deadlock acceptance test) without touching disk.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

# The blocking host collectives (and their one sanctioned wrapper) —
# the same surface R006 polices per call site. ``guarded_collective``
# counts: it EXECUTES the collective it wraps, so a rank-gated guarded
# call deadlocks exactly like a bare one.
COLLECTIVE_NAMES = ("process_allgather", "broadcast_one_to_all",
                    "sync_global_devices", "guarded_collective")

# Blocking device fetches: a D2H transfer (or a wait for one) parks the
# calling thread until the producing program completes — on a dead
# cluster that is an indefinite block, and under a lock (R017) it
# wedges every other thread contending for the lock behind device
# latency.
FETCH_NAMES = ("block_until_ready", "bulk_fetch", "device_get")

# In-place mutator methods: a call to one of these on a shared object
# is a write even though no assignment appears.
_MUTATORS = frozenset({
    "add", "append", "appendleft", "clear", "discard", "extend",
    "insert", "pop", "popitem", "popleft", "remove", "setdefault",
    "update",
})


@dataclasses.dataclass
class SharedWrite:
    """One write to shared state observed in a function body."""
    line: int
    target: str        # human-readable, e.g. "self._stalled_at"
    locked: bool       # lexically inside a `with <...lock...>:` block


@dataclasses.dataclass
class LockAcquire:
    """One ``with <lock>:`` acquisition, with the locks already held
    lexically at that point (outermost first) — the raw edges of the
    R016 lock-order graph."""
    line: int
    lock: str                  # normalized identity, e.g.
    #                            "pkg.serve.server.ScorerServer._lock"
    held: Tuple[str, ...]      # locks held when this one is taken


@dataclasses.dataclass
class LockedCall:
    """One call made while holding at least one lock (R016's
    interprocedural edges; R017's held-across-blocking-op evidence)."""
    line: int
    locks: Tuple[str, ...]     # held locks, outermost first
    basename: Optional[str]    # the called name ("device_get", ...)
    callee: Optional[str]      # resolved qualname, if provable


@dataclasses.dataclass
class FunctionInfo:
    qualname: str
    module: "ModuleInfo"
    node: ast.AST
    cls: Optional[str] = None       # enclosing class name, if a method
    parent: Optional[str] = None    # enclosing function qualname
    nested: Dict[str, str] = dataclasses.field(default_factory=dict)
    calls: Set[str] = dataclasses.field(default_factory=set)
    direct_collectives: Set[str] = dataclasses.field(default_factory=set)
    # (line, kind) per direct collective call site, in source order —
    # R015 anchors findings here; the protocol extraction orders them.
    collective_sites: List[Tuple[int, str]] = dataclasses.field(
        default_factory=list)
    direct_fetches: Set[str] = dataclasses.field(default_factory=set)
    thread_targets: Set[str] = dataclasses.field(default_factory=set)
    shared_writes: List[SharedWrite] = dataclasses.field(
        default_factory=list)
    lock_acquires: List[LockAcquire] = dataclasses.field(
        default_factory=list)
    locked_calls: List[LockedCall] = dataclasses.field(
        default_factory=list)

    @property
    def name(self) -> str:
        return self.qualname.rsplit(".", 1)[-1]


@dataclasses.dataclass
class EnvRead:
    path: str
    line: int
    var: str


@dataclasses.dataclass
class KnobRead:
    path: str
    line: int
    obj: str   # the receiver name ("cfg")
    attr: str  # the knob attribute read


@dataclasses.dataclass
class ModuleInfo:
    path: str
    modname: str
    tree: ast.Module
    source: str
    is_package: bool = False      # an __init__.py (modname IS the pkg)
    imports: Dict[str, str] = dataclasses.field(default_factory=dict)
    toplevel: Dict[str, str] = dataclasses.field(default_factory=dict)
    globals: Set[str] = dataclasses.field(default_factory=set)


class Project:
    """The parsed, resolved, summarized lint surface."""

    def __init__(self, root: str):
        self.root = root
        self.modules: Dict[str, ModuleInfo] = {}       # modname -> info
        self.by_path: Dict[str, ModuleInfo] = {}
        self.functions: Dict[str, FunctionInfo] = {}
        self.may_collectives: Dict[str, Set[str]] = {}
        self.may_locks: Dict[str, Set[str]] = {}
        self.may_fetch: Set[str] = set()
        self.thread_funcs: Set[str] = set()
        self.env_reads: List[EnvRead] = []
        self.knob_reads: List[KnobRead] = []

    # -- convenience for rules ------------------------------------------
    def module_at(self, suffix: str) -> Optional[ModuleInfo]:
        """The one module whose normalized path ends with ``suffix``."""
        suffix = suffix.replace("\\", "/")
        for m in self.by_path.values():
            if m.path.replace("\\", "/").endswith(suffix):
                return m
        return None

    def collectives_of(self, qualname: str) -> Set[str]:
        return self.may_collectives.get(qualname, set())


def package_root(directory: str) -> str:
    """Walk up out of package directories (ones holding __init__.py):
    module names must match what import statements say, so the root is
    the first NON-package ancestor — linting ``repo/pkg/sub`` alone
    must still name its modules ``pkg.sub.x``."""
    d = os.path.abspath(directory)
    while os.path.isfile(os.path.join(d, "__init__.py")):
        parent = os.path.dirname(d)
        if parent == d:
            break
        d = parent
    return d


def _modname(path: str, root: str) -> str:
    rel = os.path.relpath(os.path.abspath(path), root)
    rel = rel[:-3] if rel.endswith(".py") else rel
    parts = [p for p in rel.replace("\\", "/").split("/") if p != "."]
    if parts and parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def load_project(entries: Sequence[Tuple[str, str, ast.Module]],
                 root: Optional[str] = None) -> Project:
    """Build a Project from pre-parsed ``(path, source, tree)`` entries
    (tools/fmlint/core.py parses each file exactly once and shares the
    trees between the per-file rules and this loader)."""
    paths = [os.path.abspath(p) for p, _, _ in entries]
    if root is None:
        dirs = [os.path.dirname(p) for p in paths] or [os.getcwd()]
        root = package_root(os.path.commonpath(dirs))
    proj = Project(root)
    for path, source, tree in entries:
        mod = ModuleInfo(path=os.path.abspath(path),
                         modname=_modname(path, root),
                         tree=tree, source=source,
                         is_package=os.path.basename(path)
                         == "__init__.py")
        _collect_imports(mod)
        _collect_toplevel(mod)
        proj.modules[mod.modname] = mod
        proj.by_path[mod.path] = mod
    for mod in proj.modules.values():
        _index_functions(proj, mod)
    for fn in proj.functions.values():
        _analyze_function(proj, fn)
    _fixpoint_collectives(proj)
    _fixpoint_threads(proj)
    _fixpoint_locks(proj)
    _fixpoint_fetch(proj)
    return proj


def parse_files(paths: Sequence[str],
                overlay: Optional[Dict[str, str]] = None
                ) -> List[Tuple[str, str, ast.Module]]:
    """Parse files into loader entries, skipping unparsable ones (the
    caller reports those as R999). ``overlay`` maps absolute paths to
    replacement source — the mutant-testing seam."""
    overlay = {os.path.abspath(k): v for k, v in (overlay or {}).items()}
    out: List[Tuple[str, str, ast.Module]] = []
    for p in paths:
        ap = os.path.abspath(p)
        if ap in overlay:
            source = overlay[ap]
        else:
            with open(ap, "r", encoding="utf-8") as fh:
                source = fh.read()
        try:
            out.append((ap, source, ast.parse(source, filename=ap)))
        except SyntaxError:
            continue
    return out


# --- per-module collection -------------------------------------------------

def _collect_imports(mod: ModuleInfo) -> None:
    """Alias -> dotted-target table. Imports ANYWHERE in the module
    (this repo defers heavy imports into function bodies) are treated
    as module-wide: for call RESOLUTION that over-approximates scope
    harmlessly — a name only resolves if something imported it."""
    pkg = mod.modname.rsplit(".", 1)[0] if "." in mod.modname else ""
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                mod.imports[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0])
                if a.asname is None and "." in a.name:
                    # `import a.b.c` binds `a`, but the full dotted
                    # path is resolvable too.
                    mod.imports[a.name] = a.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = mod.modname.split(".") if mod.modname else []
                # level=1 strips the module's own name, each extra
                # level strips one more package — but an __init__.py's
                # modname IS its package (no own-name segment to
                # strip), so it drops one level fewer.
                drop = node.level - (1 if mod.is_package else 0)
                if drop > 0:
                    up = up[:len(up) - drop]
                base = ".".join(up + ([node.module]
                                      if node.module else []))
            for a in node.names:
                if a.name == "*":
                    continue
                mod.imports[a.asname or a.name] = (
                    f"{base}.{a.name}" if base else a.name)


def _collect_toplevel(mod: ModuleInfo) -> None:
    for node in mod.tree.body:
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        mod.globals.add(n.id)


def _iter_scope_children(node):
    """Direct defs of a scope, INCLUDING ones nested inside compound
    statements (a thread-target closure defined under ``if`` — the
    Watchdog/HeartbeatLease start() pattern — is still this scope's)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        child = stack.pop(0)
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
            yield child
        else:
            stack.extend(ast.iter_child_nodes(child))


def _index_functions(proj: Project, mod: ModuleInfo) -> None:
    def visit(node, prefix: str, cls: Optional[str],
              parent: Optional[FunctionInfo]):
        for child in _iter_scope_children(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}.{child.name}"
                fn = FunctionInfo(qualname=q, module=mod, node=child,
                                  cls=cls,
                                  parent=parent.qualname if parent
                                  else None)
                proj.functions[q] = fn
                if parent is not None:
                    parent.nested[child.name] = q
                elif cls is None:
                    mod.toplevel[child.name] = q
                # Nested defs keep the enclosing class context: a
                # thread-target closure inside a method closes over
                # `self`, and its `self.x()` calls must resolve.
                visit(child, q, cls, fn)
            elif isinstance(child, ast.ClassDef):
                visit(child, f"{prefix}.{child.name}", child.name,
                      parent)

    visit(mod.tree, mod.modname, None, None)


# --- per-function analysis -------------------------------------------------

def _dotted(expr) -> Optional[List[str]]:
    """["a", "b", "c"] for a pure a.b.c chain, else None."""
    parts: List[str] = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if isinstance(expr, ast.Name):
        parts.append(expr.id)
        return parts[::-1]
    return None


def resolve_call(proj: Project, fn: FunctionInfo,
                 func_expr) -> Optional[str]:
    """Qualname of the function a call expression provably targets, or
    None. See the module docstring for what 'provably' covers."""
    mod = fn.module
    if isinstance(func_expr, ast.Name):
        name = func_expr.id
        cur: Optional[FunctionInfo] = fn
        while cur is not None:      # nested defs / closures, innermost out
            if name in cur.nested:
                return cur.nested[name]
            cur = proj.functions.get(cur.parent) if cur.parent else None
        if name in mod.toplevel:
            return mod.toplevel[name]
        tgt = mod.imports.get(name)
        if tgt is not None and tgt in proj.functions:
            return tgt
        return None
    parts = _dotted(func_expr)
    if not parts or len(parts) < 2:
        return None
    if parts[0] in ("self", "cls") and fn.cls is not None and len(
            parts) == 2:
        return f"{mod.modname}.{fn.cls}.{parts[1]}"
    cls = _annotated_class(proj, fn, parts[0]) if len(parts) == 2 else None
    if cls is not None and f"{mod.modname}.{cls}.{parts[1]}" in proj.functions:
        return f"{mod.modname}.{cls}.{parts[1]}"
    # imported_module.func (or pkg.sub.func through an import alias)
    for split in range(len(parts) - 1, 0, -1):
        alias = ".".join(parts[:split])
        tgt = mod.imports.get(alias)
        if tgt is None:
            continue
        cand = ".".join([tgt] + parts[split:])
        if cand in proj.functions:
            return cand
    cand = ".".join(parts)
    return cand if cand in proj.functions else None


def _annotated_class(proj: Project, fn: FunctionInfo,
                     name: str) -> Optional[str]:
    """The class name a parameter called ``name`` is annotated with
    (``loop: StepLoop`` or ``loop: "StepLoop"``), looking from ``fn``
    outwards through its enclosing functions; None when the innermost
    parameter of that name carries no plain-name annotation."""
    cur: Optional[FunctionInfo] = fn
    while cur is not None:
        args = getattr(cur.node, "args", None)
        if args is not None:
            for a in (args.posonlyargs + args.args + args.kwonlyargs):
                if a.arg != name:
                    continue
                ann = a.annotation
                if isinstance(ann, ast.Constant) and isinstance(
                        ann.value, str):
                    return ann.value
                return ann.id if isinstance(ann, ast.Name) else None
        cur = proj.functions.get(cur.parent) if cur.parent else None
    return None


def _call_basename(func_expr) -> Optional[str]:
    if isinstance(func_expr, ast.Name):
        return func_expr.id
    if isinstance(func_expr, ast.Attribute):
        return func_expr.attr
    return None


def _is_lock_expr(expr) -> bool:
    """``with self._lock:`` / ``with LOCK:`` — any name in the context
    manager chain containing 'lock' (case-insensitive) counts as
    holding the owning lock."""
    for n in ast.walk(expr):
        name = None
        if isinstance(n, ast.Name):
            name = n.id
        elif isinstance(n, ast.Attribute):
            name = n.attr
        if name is not None and "lock" in name.lower():
            return True
    return False


def lock_identity(fn: FunctionInfo, expr) -> Optional[str]:
    """Normalized identity of the lock a ``with`` item holds, for the
    R016 lock graph: ``self._lock`` in a method of C in module m is
    ``m.C._lock`` (every instance shares the ordering discipline, so
    instances collapse into their class), a module-global ``_lock`` is
    ``m._lock``, and an imported module's lock resolves through the
    import table. Returns None when no lock-ish name is present."""
    mod = fn.module
    parts = _dotted(expr)
    if parts is None:
        # Subscripted / computed manager (`with self._locks[i]:`):
        # anchor on the first lock-ish name found.
        for n in ast.walk(expr):
            name = None
            if isinstance(n, ast.Name):
                name = n.id
            elif isinstance(n, ast.Attribute):
                name = n.attr
            if name is not None and "lock" in name.lower():
                return f"{mod.modname}.{name}"
        return None
    if parts[0] in ("self", "cls"):
        owner = fn.cls if fn.cls is not None else fn.name
        return ".".join([mod.modname, owner] + parts[1:])
    tgt = mod.imports.get(parts[0])
    if tgt is not None and len(parts) > 1:
        return ".".join([tgt] + parts[1:])
    return ".".join([mod.modname] + parts)


def _analyze_function(proj: Project, fn: FunctionInfo) -> None:
    """One pass over the function's OWN statements (nested defs are
    their own FunctionInfo) collecting calls, collective seeds, thread
    targets, shared writes, lock scopes, and env/knob reads."""
    own_nested = {proj.functions[q].node for q in fn.nested.values()}

    def walk(node, held: Tuple[str, ...]):
        for child in ast.iter_child_nodes(node):
            if child not in own_nested:
                handle(child, held)

    def handle(child, held: Tuple[str, ...]):
        if isinstance(child, ast.With):
            inner = held
            for item in child.items:
                walk(item, held)
                if _is_lock_expr(item.context_expr):
                    lid = lock_identity(fn, item.context_expr)
                    if lid is not None:
                        fn.lock_acquires.append(LockAcquire(
                            line=child.lineno, lock=lid, held=inner))
                        inner = inner + (lid,)
            for s in child.body:
                # Through handle(), not walk(): a With nested directly
                # in this body must get its own held-locks branch.
                handle(s, inner)
            return
        _visit(child, held)
        walk(child, held)

    def record_write(node, target: str, held: Tuple[str, ...]):
        fn.shared_writes.append(SharedWrite(
            line=node.lineno, target=target, locked=bool(held)))

    declared_global: Set[str] = set()
    for n in ast.walk(fn.node):
        if isinstance(n, ast.Global):
            declared_global.update(n.names)

    def _visit(child, held: Tuple[str, ...]):
        if isinstance(child, ast.Call):
            callee = resolve_call(proj, fn, child.func)
            if callee is not None:
                fn.calls.add(callee)
            base = _call_basename(child.func)
            if base in COLLECTIVE_NAMES:
                fn.direct_collectives.add(base)
                fn.collective_sites.append((child.lineno, base))
            if base in FETCH_NAMES:
                fn.direct_fetches.add(base)
            if held and (base is not None or callee is not None):
                fn.locked_calls.append(LockedCall(
                    line=child.lineno, locks=held, basename=base,
                    callee=callee))
            if base == "Thread":
                for kw in child.keywords:
                    if kw.arg == "target":
                        tgt = resolve_call(proj, fn, kw.value)
                        if tgt is not None:
                            fn.thread_targets.add(tgt)
            # in-place mutators on self attrs / module globals
            if (isinstance(child.func, ast.Attribute)
                    and child.func.attr in _MUTATORS):
                parts = _dotted(child.func.value)
                if parts and parts[0] == "self" and len(parts) >= 2:
                    record_write(child, ".".join(parts), held)
                elif (parts and len(parts) == 1
                      and parts[0] in fn.module.globals):
                    record_write(child, parts[0], held)
            _scan_env_read(proj, fn, child)
        elif isinstance(child, (ast.Assign, ast.AugAssign)):
            targets = (child.targets if isinstance(child, ast.Assign)
                       else [child.target])
            for t in targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Attribute):
                        # Store ctx only: `buf[self.idx] = 1` READS
                        # self.idx, and in `self.a.b = 1` only the
                        # outermost attribute is the write.
                        if not isinstance(n.ctx, ast.Store):
                            continue
                        parts = _dotted(n)
                        if parts and parts[0] == "self":
                            record_write(child, ".".join(parts),
                                         held)
                    elif (isinstance(n, ast.Name)
                          and isinstance(getattr(n, "ctx", None),
                                         ast.Store)
                          and n.id in declared_global):
                        record_write(child, n.id, held)
            # subscript store on a module global: G[k] = v
            for t in targets:
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Name)
                        and t.value.id in fn.module.globals
                        and t.value.id not in declared_global):
                    record_write(child, t.value.id, held)
        elif isinstance(child, ast.Attribute):
            _scan_knob_read(proj, fn, child)

    walk(fn.node, ())


def _scan_env_read(proj: Project, fn: FunctionInfo,
                   call: ast.Call) -> None:
    """os.environ.get("FM_X") / os.getenv("FM_X") reads."""
    parts = _dotted(call.func)
    if not parts:
        return
    is_env_get = (parts[-2:] == ["environ", "get"]
                  or parts[-1] == "getenv")
    if not is_env_get or not call.args:
        return
    arg = call.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        if arg.value.startswith("FM_"):
            proj.env_reads.append(EnvRead(
                path=fn.module.path, line=call.lineno, var=arg.value))


def _scan_knob_read(proj: Project, fn: FunctionInfo,
                    node: ast.Attribute) -> None:
    """``cfg.<attr>`` attribute reads (receiver conventionally named
    cfg/config) — R009 checks them against the FmConfig surface."""
    if (isinstance(node.value, ast.Name)
            and node.value.id in ("cfg", "config")
            and isinstance(node.ctx, ast.Load)):
        proj.knob_reads.append(KnobRead(
            path=fn.module.path, line=node.lineno,
            obj=node.value.id, attr=node.attr))


# --- fixpoints -------------------------------------------------------------

def _fixpoint_collectives(proj: Project) -> None:
    may = {q: set(f.direct_collectives)
           for q, f in proj.functions.items()}
    changed = True
    while changed:
        changed = False
        for q, f in proj.functions.items():
            for callee in f.calls:
                extra = may.get(callee)
                if extra and not extra <= may[q]:
                    may[q] |= extra
                    changed = True
    proj.may_collectives = may


def _fixpoint_threads(proj: Project) -> None:
    on_thread: Set[str] = set()
    for f in proj.functions.values():
        on_thread |= f.thread_targets
    changed = True
    while changed:
        changed = False
        for q in list(on_thread):
            f = proj.functions.get(q)
            if f is None:
                continue
            for callee in f.calls:
                if callee in proj.functions and callee not in on_thread:
                    on_thread.add(callee)
                    changed = True
    proj.thread_funcs = on_thread


def _fixpoint_locks(proj: Project) -> None:
    """``may_locks[q]`` — locks a call to ``q`` may transitively
    acquire (the R016 interprocedural edge source)."""
    may = {q: {a.lock for a in f.lock_acquires}
           for q, f in proj.functions.items()}
    changed = True
    while changed:
        changed = False
        for q, f in proj.functions.items():
            for callee in f.calls:
                extra = may.get(callee)
                if extra and not extra <= may[q]:
                    may[q] |= extra
                    changed = True
    proj.may_locks = may


def _fixpoint_fetch(proj: Project) -> None:
    """Functions that may (transitively) execute a blocking device
    fetch (FETCH_NAMES) — R017's held-across-fetch reachability."""
    fetch = {q for q, f in proj.functions.items() if f.direct_fetches}
    changed = True
    while changed:
        changed = False
        for q, f in proj.functions.items():
            if q in fetch:
                continue
            if any(c in fetch for c in f.calls):
                fetch.add(q)
                changed = True
    proj.may_fetch = fetch


# --- protocol extraction ---------------------------------------------------
#
# The collective-protocol model (R014, `python -m tools.fmlint
# --protocol`): each function's body is read as an ordered sequence of
# collective operations. A direct call site becomes a concrete op
# token — the collective kind plus its static ``label=`` where one is
# written (`guarded_collective[lockstep/window_fill]`) — and a resolved
# call into a function that may itself execute collectives becomes an
# opaque sub-protocol token (`ckpt._broadcast_int()`): its INTERNAL
# order is that function's own protocol, checked where it is defined.
# Rank-invariance of a whole driver entry point then decomposes into a
# per-branch-point obligation: at every conditional either both arms
# carry the same op sequence, or the condition is rank-uniform
# (broadcast-produced / process_count / constant) — which is exactly
# what R014 discharges branch by branch.

def _static_label(call: ast.Call) -> Optional[str]:
    for kw in call.keywords:
        if kw.arg == "label" and isinstance(kw.value, ast.Constant) \
                and isinstance(kw.value.value, str):
            return kw.value.value
    return None


def op_token(proj: Project, fn: FunctionInfo,
             call: ast.Call) -> Optional[str]:
    """The protocol-op token for one call node, or None if the call
    provably executes no collective."""
    base = _call_basename(call.func)
    if base in COLLECTIVE_NAMES:
        label = _static_label(call)
        return f"{base}[{label}]" if label else base
    callee = resolve_call(proj, fn, call.func)
    if callee is not None and proj.collectives_of(callee):
        return f"{callee}()"
    return None


def collective_ops(proj: Project, fn: FunctionInfo,
                   stmts: Sequence[ast.stmt]) -> List[str]:
    """Ordered op tokens for a statement list (position-sorted, nested
    defs excluded: defining a closure executes nothing)."""
    found: List[Tuple[int, int, str]] = []

    def visit(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            if isinstance(child, ast.Call):
                tok = op_token(proj, fn, child)
                if tok is not None:
                    found.append((child.lineno, child.col_offset, tok))
            visit(child)

    for stmt in stmts:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        if isinstance(stmt, ast.Call):  # bare-expression guard
            tok = op_token(proj, fn, stmt)
            if tok is not None:
                found.append((stmt.lineno, stmt.col_offset, tok))
        visit(stmt)
    return [t for _, _, t in sorted(found)]


def protocol_automaton(proj: Project, qualname: str,
                       depth: int = 2) -> List[str]:
    """Human-readable protocol automaton for one entry point: the
    ordered collective ops with branch/loop/try structure, sub-protocol
    calls inlined ``depth`` levels deep. The ``--protocol`` CLI view —
    what a reviewer used to reconstruct by hand for every PR touching
    the multi-process layer."""
    fn = proj.functions.get(qualname)
    if fn is None:
        return [f"<unknown function {qualname}>"]
    lines: List[str] = [f"protocol of {qualname}:"]
    seen: Set[str] = {qualname}

    def emit(ctx: FunctionInfo, stmts: Sequence[ast.stmt],
             indent: int, d: int) -> None:
        pad = "  " * indent
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue
            own_ops = collective_ops(proj, ctx, [stmt])
            if not own_ops:
                continue
            if isinstance(stmt, ast.If):
                lines.append(f"{pad}if <line {stmt.lineno}>:")
                emit(ctx, stmt.body, indent + 1, d)
                if stmt.orelse:
                    lines.append(f"{pad}else:")
                    emit(ctx, stmt.orelse, indent + 1, d)
            elif isinstance(stmt, (ast.While, ast.For)):
                kind = ("while" if isinstance(stmt, ast.While)
                        else "for")
                lines.append(f"{pad}{kind} <line {stmt.lineno}>:")
                emit(ctx, stmt.body, indent + 1, d)
                if stmt.orelse:
                    lines.append(f"{pad}else:")
                    emit(ctx, stmt.orelse, indent + 1, d)
            elif isinstance(stmt, ast.Try):
                lines.append(f"{pad}try:")
                emit(ctx, stmt.body, indent + 1, d)
                for h in stmt.handlers:
                    lines.append(f"{pad}except <line {h.lineno}>:")
                    emit(ctx, h.body, indent + 1, d)
                if stmt.orelse:
                    lines.append(f"{pad}else:")
                    emit(ctx, stmt.orelse, indent + 1, d)
                if stmt.finalbody:
                    lines.append(f"{pad}finally:")
                    emit(ctx, stmt.finalbody, indent + 1, d)
            elif isinstance(stmt, ast.With):
                emit(ctx, stmt.body, indent, d)
            else:
                for tok in own_ops:
                    inlined = False
                    if tok.endswith("()") and d > 0:
                        callee = tok[:-2]
                        sub = proj.functions.get(callee)
                        if sub is not None and callee not in seen:
                            seen.add(callee)
                            lines.append(f"{pad}{tok} -> inlined:")
                            emit(sub, sub.node.body, indent + 1, d - 1)
                            inlined = True
                    if not inlined:
                        lines.append(f"{pad}{tok}")
    emit(fn, fn.node.body, 1, depth)
    return lines
