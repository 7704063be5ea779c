"""Device-busy time of one named part of the step: inside one
execution of the named programs, the operations whose jax op path has
``scope`` as a component (``jit(fm_train_step)/adagrad/scatter-add``,
also inside ``jvp(...)`` and ``transpose(jvp(...))``), median over the
traced executions, by the interval arithmetic of ``program_device_ms``.
``scope: null`` reads the operations with none of the program's scopes
(XLA's own layout copies carry ``table:``, ``acc:`` or nothing).

The op path sits on the event *metadata* of the chip's ``XLA Ops``
(stat ``tf_op``), which ``ProfileData`` hides: ``xplane_meta`` reads it
from the run's ``.xplane.pb``. The scopes are the program's
(``jax.named_scope`` in ops/interaction.py, ops/pallas_fm.py and
models/fm.py). A step none of whose operations carries one (a program
from before the scopes, or an executable a compile cache kept from
then) reads None, said on a line.

The first call of a run also prints every scope's time, the bytes XLA
says its operations access (``bytes_accessed``) and the GB/s the two
make: a line to read, not a metric."""

import bisect
import glob
import os
import re
import statistics

from benchmarks import trace_reduce, xplane_meta
from benchmarks.harness import say

SCOPES = ("dedup", "gather", "expand", "interaction", "loss", "adagrad")
# A scope as a path component: bare, or inside transformations
# (``transpose(jvp(expand))``); ``jit(gather)`` is a function's name.
_COMPONENT = re.compile(r"^(?:(?!jit\()[\w.]+\()*("
                        + "|".join(SCOPES) + r")\)*$")


def scope_of(op_path):
    """The innermost of the program's scopes on a ``tf_op`` path
    (``<scopes>/<primitive>:<type>``), or None. The last component is
    the primitive (``gather`` is one) and is no scope."""
    found = None
    for part in str(op_path or "").rsplit(":", 1)[0].split("/")[:-1]:
        m = _COMPONENT.match(part)
        if m:
            found = m.group(1)
    return found


def _xplane_path(ctx):
    if "xplane_path" in ctx:
        return ctx["xplane_path"]
    files = glob.glob(os.path.join(ctx["run"].work_dir, "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def by_scope(trace, meta, programs):
    """{scope or None: (median ms, median bytes_accessed)} over the
    whole executions of ``programs``; {} if none was traced."""
    runs = []
    for d in trace.devices:
        stats_of = meta.get(d.name, {})
        ops = sorted(d.ops, key=lambda o: o.start)
        starts = [o.start for o in ops]
        for m in d.modules:
            if trace_reduce.program_name(m.name) not in programs:
                continue
            spans, nbytes = {}, {}
            i = bisect.bisect_left(starts, m.start)
            while i < len(ops) and ops[i].start < m.end:
                o = ops[i]
                i += 1
                st = stats_of.get(o.name, {})
                s = scope_of(st.get("tf_op"))
                spans.setdefault(s, []).append((o.start, min(o.end, m.end)))
                nbytes[s] = nbytes.get(s, 0) + int(
                    st.get("bytes_accessed", 0))
            if spans:
                runs.append({s: (trace_reduce.union_length(v), nbytes[s])
                             for s, v in spans.items()})
    out = {}
    for s in {s for r in runs for s in r}:
        out[s] = (1e3 * statistics.median(r.get(s, (0.0, 0))[0]
                                          for r in runs),
                  statistics.median(r.get(s, (0.0, 0))[1] for r in runs))
    return out


def _table(ctx, programs):
    key = "scope_device_ms:" + ",".join(programs)
    if key in ctx:
        return ctx[key]
    ctx[key] = table = {}
    path = _xplane_path(ctx)
    if path is None:
        say("scopes: no .xplane.pb to read the op paths from")
        return table
    found = by_scope(ctx["trace"], xplane_meta.read(path), programs)
    if not found:
        return table
    if not any(s in SCOPES for s in found):
        say(f"scopes: no operation of {'/'.join(programs)} carries a "
            f"scope ({', '.join(SCOPES)}) on its op path: a program "
            "from before the scopes, or an executable a compile cache "
            "kept from then; the scope metrics are left out")
        return table
    table.update(found)
    whole = ctx["trace"].program_device_ms(programs)
    for s in SCOPES + (None,):
        ms, nb = found.get(s, (0.0, 0))
        rate = f"{nb / ms / 1e6:.1f} GB/s" if ms else "no time"
        say(f"scope {s or 'unscoped'}: {ms:.3f} ms a step, XLA "
            f"bytes_accessed {nb / 1e9:.4f} GB, {rate}")
    say(f"scopes together {sum(v[0] for v in found.values()):.3f} ms "
        f"of the step's {whole:.3f} ms device time")
    return table


def read(ctx, programs, scope):
    table = _table(ctx, programs)
    if not table:
        return None
    return table.get(scope, (0.0, 0))[0]
