"""Parallel host data plane == serial data plane, bit for bit.

The ``host_threads`` knob must be a PURE throughput knob: for the same
config/seed, the multi-worker plane (group scanner -> worker pool ->
bounded ordered ring -> shared emitter) must emit the byte-identical
batch stream the serial pipeline emits — across the C++ fast path, the
tolerant generic path, spill-requeued tails (fixed-U mode), weight
sidecars, keep_empty, raw-ids mode, sharded input, multi-file
multi-epoch shuffle, and error provenance. Plus: the pool must never
leak worker threads (clean end OR abandoned iterator), and the
4-worker build must actually scale (the tier-1 smoke the BENCH row
pins locally)."""

import json
import os
import threading
import time

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data import cparser
from fast_tffm_tpu.data.badlines import BadLineTracker
from fast_tffm_tpu.data.parser import ParseError
from fast_tffm_tpu.data.pipeline import (SpillStats, batch_iterator,
                                         resolve_host_threads)

needs_cpp = pytest.mark.skipif(not cparser.available(),
                               reason="C++ parser extension unavailable")


def _write(tmp_path, n=300, seed=1, name="d.txt", blanks=False,
           nnz_hi=14, vocab=300):
    rng = np.random.default_rng(seed)
    lines = []
    for i in range(n):
        nnz = rng.integers(1, nnz_hi)
        ids = rng.choice(vocab, size=nnz, replace=False)
        lines.append(" ".join(["1" if rng.random() < 0.4 else "0"]
                              + [f"{j}:{rng.random():.4f}" for j in ids]))
        if blanks and i % 11 == 3:
            lines.append("")   # blank line
        if blanks and i % 29 == 7:
            lines.append("   ")  # whitespace-only line
    p = tmp_path / name
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def _cfg(path, host_threads, **kw):
    base = dict(vocabulary_size=300, factor_num=4, batch_size=16,
                train_files=(path,), shuffle=False,
                bucket_ladder=(4, 8, 16), max_features_per_example=16,
                host_threads=host_threads)
    base.update(kw)
    return FmConfig(**base)


def _key(b):
    """Full byte identity of one DeviceBatch."""
    return (b.labels.tobytes(), b.weights.tobytes(),
            None if b.uniq_ids is None else b.uniq_ids.tobytes(),
            b.local_idx.tobytes(), b.vals.tobytes(),
            None if b.fields is None else b.fields.tobytes(),
            b.num_real)


def _stream(cfg, **kw):
    return [_key(b) for b in batch_iterator(cfg, cfg.train_files,
                                            training=True, **kw)]


def _assert_parity(path, cfg_kw=None, it_kw=None):
    cfg_kw, it_kw = cfg_kw or {}, it_kw or {}
    a = _stream(_cfg(path, 1, **cfg_kw), **it_kw)
    b = _stream(_cfg(path, 4, **cfg_kw), **it_kw)
    assert len(a) == len(b) and a == b
    return a


@needs_cpp
def test_fast_path_parity(tmp_path):
    s = _assert_parity(_write(tmp_path))
    assert len(s) == 19  # 300 examples / B=16


@needs_cpp
def test_fast_path_parity_shuffle(tmp_path):
    # Shuffle rng (file order, window draws, per-batch row perms) is
    # shared emitter code fed in ring order — identical draws.
    p1 = _write(tmp_path, n=150, seed=2)
    p2 = _write(tmp_path, n=90, seed=3, name="e.txt")
    _assert_parity(p1, cfg_kw=dict(train_files=(p1, p2), shuffle=True,
                                   seed=5, queue_size=64),
                   it_kw=dict(epochs=2, seed=11))


def _rows(b):
    """A batch's real rows as a sorted multiset of (label, weight, the
    table rows its cells name, values, fields): what a permutation of
    the rows leaves alone. Cells are read through ``uniq_ids``, so a
    mesh feed's re-pointed slots compare by the rows they mean."""
    n = b.num_real
    ids = b.local_idx[:n] if b.uniq_ids is None else np.asarray(
        b.uniq_ids)[b.local_idx[:n]]
    return sorted(
        (float(b.labels[r]), float(b.weights[r]), ids[r].tobytes(),
         b.vals[r].tobytes(),
         None if b.fields is None else b.fields[r].tobytes())
        for r in range(n))


@needs_cpp
@pytest.mark.parametrize("kw", [
    dict(),                                             # one device's feed
    dict(mesh=4),                                       # a mesh's: remap
    dict(model_type="ffm", field_num=4),                # fields move too
    dict(raw_ids=True),
])
@pytest.mark.parametrize("workers", [1, 4])
def test_a_shuffled_batch_is_a_row_permutation_of_the_unshuffled_one(
        tmp_path, kw, workers):
    """ISSUE 46: the shuffle's row order is written by the builder's
    finish(), from (the epoch's seed, the batch's number in the stream).
    The window over whole batches may hand them over in another order,
    so batches pair by their examples: a shuffled batch holds one
    unshuffled batch's examples, each whole (label, cells, values,
    fields; a mesh feed's cells re-pointed alike), in another order;
    the padding block of the short last batch stays at the tail with
    weight 0; the unique slots do not move."""
    from fast_tffm_tpu.data.pipeline import RowShards
    kw = dict(kw)
    mesh, raw = kw.pop("mesh", 0), kw.pop("raw_ids", False)
    path = _write(tmp_path, n=150, seed=8)
    if kw.get("model_type") == "ffm":
        lines = open(path).read().split("\n")
        open(path, "w").write("\n".join(
            " ".join([ln.split()[0]] + [f"{i % 4}:{tok}" for i, tok in
                                        enumerate(ln.split()[1:])])
            for ln in lines if ln) + "\n")

    def batches(shuffle, seed=11):
        cfg = _cfg(path, workers, shuffle=shuffle, queue_size=16, **kw)
        return list(batch_iterator(
            cfg, cfg.train_files, training=True, epochs=1, seed=seed,
            raw_ids=raw, row_shards=RowShards.of(cfg, mesh) if mesh
            else None))

    plain, mixed = batches(False), batches(True)
    assert len(plain) == len(mixed) == 10 and plain[-1].num_real == 6
    by_rows = {repr(_rows(b)): b for b in plain}
    assert len(by_rows) == 10
    moved = 0
    for b in mixed:
        a = by_rows.pop(repr(_rows(b)))     # the same examples, each whole
        n = b.num_real
        assert n == a.num_real
        if a.uniq_ids is not None:
            np.testing.assert_array_equal(b.uniq_ids, a.uniq_ids)
        assert b.row_shards == a.row_shards == (mesh or 1)
        # the padding block: at the tail, as the unshuffled batch has it
        np.testing.assert_array_equal(b.weights, a.weights)
        assert b.weights[:n].all() and not b.weights[n:].any()
        for name in ("labels", "local_idx", "vals"):
            np.testing.assert_array_equal(getattr(b, name)[n:],
                                          getattr(a, name)[n:])
        moved += not np.array_equal(b.local_idx, a.local_idx)
    assert not by_rows and moved >= 9
    # one seed repeats, another seed and another epoch's differ
    again, other = batches(True), batches(True, seed=12)
    assert [_key(b) for b in again] == [_key(b) for b in mixed]
    assert [_key(b) for b in other] != [_key(b) for b in mixed]
    cfg = _cfg(path, workers, shuffle=True, queue_size=16, **kw)
    two = list(batch_iterator(cfg, cfg.train_files, training=True, epochs=2,
                              seed=11, raw_ids=raw))
    if not mesh:
        assert [_key(b) for b in two[:10]] == [_key(b) for b in mixed]
    assert sorted(map(repr, map(_rows, two[10:]))) == sorted(
        map(repr, map(_rows, two[:10])))
    assert [_key(b) for b in two[10:]] != [_key(b) for b in two[:10]]


@needs_cpp
@pytest.mark.parametrize("workers", [1, 4])
def test_a_plane_that_does_not_train_is_not_permuted(tmp_path, workers):
    """``training=False`` (a validation sweep, predict): shuffle on in
    the configuration, the lines in file order all the same."""
    path = _write(tmp_path, n=100, seed=9)
    on = _cfg(path, workers, shuffle=True, queue_size=16)
    off = _cfg(path, workers, shuffle=False)
    assert [_key(b) for b in batch_iterator(on, on.train_files,
                                            training=False)] == [
        _key(b) for b in batch_iterator(off, off.train_files,
                                        training=False)]


@needs_cpp
def test_emit_gathers_nothing(tmp_path):
    """The emitting thread permutes no array: a batch is permuted once,
    where the builder pads it out (``fm_bb_finish``), and the arrays
    ``_emit`` is handed are the arrays the batch ships."""
    import inspect
    from fast_tffm_tpu.data import pipeline
    src = inspect.getsource(pipeline._BatchEmitter._emit)
    assert "perm" not in src and "nprng" not in src
    emitter = pipeline._BatchEmitter(
        _cfg(_write(tmp_path), 1, shuffle=True), 16, 16, False, 0, True,
        3, None)
    bb = pipeline._make_builder(emitter.cfg, 16, False, False, False, 0)
    bb.feed(open(emitter.cfg.train_files[0], "rb").read())
    out = emitter.finish(bb)
    batch = emitter._emit(*out)
    assert batch.labels is out[1] and batch.local_idx is out[3]
    assert batch.vals is out[4]


@needs_cpp
def test_fast_path_parity_keep_empty(tmp_path):
    s = _assert_parity(_write(tmp_path, blanks=True),
                       it_kw=dict(keep_empty=True))
    assert s  # blank lines became zero-feature examples in both


@needs_cpp
def test_fast_path_parity_raw_ids(tmp_path):
    _assert_parity(_write(tmp_path), it_kw=dict(raw_ids=True))


@needs_cpp
def test_fast_path_parity_sharded(tmp_path):
    path = _write(tmp_path, n=400, seed=4)
    for shard in range(3):
        _assert_parity(path, it_kw=dict(shard_index=shard,
                                        num_shards=3))


@needs_cpp
def test_spill_requeued_tail_parity(tmp_path):
    """Fixed-U mode: a unique-budget spill closes a batch early and the
    tail reopens the next one — the parallel plane must replay that
    requeue exactly (invalidate in-flight groups, re-cut from the
    spilled line), with identical spill accounting."""
    path = _write(tmp_path, n=500, seed=6, nnz_hi=16, vocab=3000)
    stats = {}
    streams = {}
    for w in (1, 4):
        cfg = _cfg(path, w, vocabulary_size=3000, batch_size=32)
        st = SpillStats()
        streams[w] = [_key(b) for b in batch_iterator(
            cfg, cfg.train_files, training=True, fixed_shape=True,
            uniq_bucket=128, stats=st)]
        stats[w] = st
    assert streams[1] == streams[4]
    # The config is built to spill hard; if it stops spilling the test
    # stops testing the rewind protocol — fail loudly instead.
    assert stats[1].spilled_batches > 3
    for f in ("batches", "spilled_batches", "real_examples", "max_uniq"):
        assert getattr(stats[1], f) == getattr(stats[4], f), f


@needs_cpp
def test_weight_sidecar_parity(tmp_path):
    # Weighted input pairs weights to lines in Python (GIL-bound): it
    # stays on the serial plane at every host_threads — parity is the
    # pin that the routing actually does that.
    path = _write(tmp_path, n=120, seed=7)
    wpath = tmp_path / "w.txt"
    wpath.write_text("".join(f"{v:.3f}\n" for v in
                             np.random.default_rng(0).uniform(
                                 0.5, 2.0, 120)))
    _assert_parity(path, it_kw=dict(weight_files=(str(wpath),)))


@needs_cpp
def test_quarantine_parity_and_global_dedupe(tmp_path):
    """Tolerant generic plane: identical batch streams, and the
    run-scoped tracker stays GLOBAL across workers — same bad/total
    counts, same per-file attribution, and the quarantine sidecar
    holds the same RECORD SET (order may interleave across workers;
    each (file, lineno) exactly once even over 2 epochs)."""
    path = _write(tmp_path, n=260, seed=8)
    lines = open(path).read().splitlines()
    for i in range(7, 260, 40):
        lines[i] = f"##bad## {lines[i]}"
    dirty = tmp_path / "dirty.txt"
    dirty.write_text("\n".join(lines) + "\n")
    results = {}
    for w in (1, 4):
        qfile = str(tmp_path / f"q{w}.jsonl")
        tracker = BadLineTracker("quarantine", 0.5,
                                 quarantine_file=qfile)
        cfg = _cfg(str(dirty), w, bad_line_policy="quarantine",
                   max_bad_fraction=0.5)
        stream = [_key(b) for b in batch_iterator(
            cfg, cfg.train_files, training=True, epochs=2,
            bad_lines=tracker)]
        tracker.close()
        recs = [json.loads(ln) for ln in open(qfile) if ln.strip()]
        results[w] = (stream, tracker.bad, tracker.total,
                      dict(tracker.by_file),
                      sorted((r["file"], r["lineno"], r["raw"])
                             for r in recs))
    assert results[1] == results[4]
    assert results[1][1] == 2 * 7  # 7 bad lines, counted both epochs
    assert len(results[1][4]) == 7  # quarantined ONCE across epochs


@needs_cpp
def test_parallel_generic_plane_actually_runs(tmp_path):
    """The quarantine config above must really fan out: fm-build
    workers alive while the iterator is draining."""
    path = _write(tmp_path, n=200, seed=9)
    cfg = _cfg(path, 4, bad_line_policy="quarantine")
    it = batch_iterator(cfg, cfg.train_files, training=True)
    next(it)
    alive = [t.name for t in threading.enumerate()
             if t.name.startswith("fm-build") and t.is_alive()]
    it.close()
    assert alive, "generic parallel plane never started its pool"


@needs_cpp
def test_error_provenance_parity(tmp_path):
    """A bad line under policy=error must raise the SAME file/lineno
    diagnosis from the parallel plane as from the serial one (worker
    errors rebase builder-relative linenos onto the stream)."""
    path = _write(tmp_path, n=90, seed=10)
    lines = open(path).read().splitlines()
    lines[61] = "notalabel 3:1"
    bad = tmp_path / "bad.txt"
    bad.write_text("\n".join(lines) + "\n")
    msgs = {}
    for w in (1, 4):
        cfg = _cfg(str(bad), w)
        with pytest.raises(ParseError) as ei:
            list(batch_iterator(cfg, cfg.train_files, training=True))
        msgs[w] = str(ei.value)
    assert msgs[1] == msgs[4]
    assert "line 62" in msgs[1] and "bad.txt" in msgs[1]


@needs_cpp
def test_no_worker_leak_on_completion_and_abandon(tmp_path):
    path = _write(tmp_path, n=200, seed=12)

    def leaked():
        return [t for t in threading.enumerate()
                if t.name.startswith("fm-build") and t.is_alive()]

    cfg = _cfg(path, 4)
    list(batch_iterator(cfg, cfg.train_files, training=True))
    assert not leaked()
    # Abandoned mid-stream: generator close must stop and join the pool.
    it = batch_iterator(cfg, cfg.train_files, training=True)
    next(it)
    it.close()
    assert not leaked()


@needs_cpp
@pytest.mark.parametrize("chunk_bytes", [7, 64, 1000, 1 << 20])
def test_group_scanner_cuts_the_same_groups_at_any_chunk(tmp_path,
                                                         monkeypatch,
                                                         chunk_bytes):
    """A group is scanned once, the scan resuming where it stopped as
    chunks are appended: whatever the chunk size, the groups are the
    file's example lines B at a time, blank lines carried along, and
    the parallel plane's stream is the serial one."""
    from fast_tffm_tpu.data import pipeline as pl
    path = _write(tmp_path, n=120, seed=5, blanks=True)
    real = pl._iter_owned_chunks
    monkeypatch.setattr(
        pl, "_iter_owned_chunks",
        lambda p, s, e, chunk_bytes_=chunk_bytes, **kw: real(
            p, s, e, **{**kw, "chunk_bytes": chunk_bytes_}))
    sc = pl._GroupScanner([path, path], 0, 1, 16, False, None)
    groups = list(iter(sc.next_group, None))
    data = open(path, "rb").read()
    assert b"".join(g.blob for g in groups) == (data + data).rstrip()+b"\n"
    for g in groups[:-1]:
        assert sum(bool(ln.strip()) for ln in g.blob.splitlines()) == 16
    assert [g.line_start for g in groups] == list(np.cumsum(
        [0] + [g.lines for g in groups[:-1]]))
    _assert_parity(path)


@needs_cpp
def test_a_built_batch_is_emitted_before_the_ring_is_filled(tmp_path,
                                                            monkeypatch):
    """The coordinator stops filling the ring when the batch at its head
    is built: an epoch's first batch does not wait for the cutting of
    ``depth`` groups (8 here, each made slow), only for its own."""
    from fast_tffm_tpu.data import pipeline as pl
    path = _write(tmp_path, n=400, seed=14)
    cut = []
    real = pl._GroupScanner.next_group

    def slow(self):
        time.sleep(0.05)
        cut.append(1)
        return real(self)
    monkeypatch.setattr(pl._GroupScanner, "next_group", slow)
    cfg = _cfg(path, 4)
    # fixed U: the scanner is driven inline, no thread cuts ahead
    it = batch_iterator(cfg, cfg.train_files, training=True,
                        fixed_shape=True, uniq_bucket=280)
    next(it)
    assert len(cut) <= 3
    rest = list(it)
    monkeypatch.undo()
    want = list(batch_iterator(_cfg(path, 1), cfg.train_files,
                               training=True, fixed_shape=True,
                               uniq_bucket=280))
    assert len(rest) + 1 == len(want) == 25


def test_read_ahead_orders_raises_and_stops():
    """_read_ahead: the items in order, what the source raises raised at
    the consumer, and a closed consumer stops the thread."""
    from fast_tffm_tpu.data.pipeline import _read_ahead

    def alive(name):
        return [t for t in threading.enumerate() if t.name == name]

    assert list(_read_ahead(iter(range(50)), 2, "ra-all")) == list(
        range(50))

    def bad():
        yield 1
        raise KeyError("from the source")
    it = _read_ahead(bad(), 2, "ra-bad")
    assert next(it) == 1
    with pytest.raises(KeyError, match="from the source"):
        next(it)
    it = _read_ahead(iter(range(10 ** 9)), 2, "ra-closed")
    assert next(it) == 0
    it.close()
    deadline = time.monotonic() + 5
    while (alive("ra-closed") or alive("ra-all") or alive("ra-bad")) \
            and time.monotonic() < deadline:
        time.sleep(0.05)
    assert not (alive("ra-closed") or alive("ra-all") or alive("ra-bad"))


@needs_cpp
def test_scan_thread_stops_with_the_iterator(tmp_path):
    """The scanner's read-ahead thread (fm-scan) ends with its epoch and
    with an abandoned iterator, like the build pool."""
    path = _write(tmp_path, n=400, seed=13)
    cfg = _cfg(path, 4)

    def scanning():
        return [t for t in threading.enumerate()
                if t.name == "fm-scan" and t.is_alive()]

    def gone():
        deadline = time.monotonic() + 5
        while scanning() and time.monotonic() < deadline:
            time.sleep(0.05)
        return not scanning()

    it = batch_iterator(cfg, cfg.train_files, training=True)
    next(it)
    it.close()
    assert gone()
    list(batch_iterator(cfg, cfg.train_files, training=True))
    assert gone()


def test_resolve_host_threads():
    path_free = dict(vocabulary_size=8, batch_size=4)
    assert resolve_host_threads(FmConfig(host_threads=3,
                                         **path_free)) == 3
    assert resolve_host_threads(FmConfig(host_threads=1,
                                         **path_free)) == 1
    auto = resolve_host_threads(FmConfig(host_threads=0, **path_free))
    assert 1 <= auto <= 4
    with pytest.raises(ValueError):
        FmConfig(host_threads=-1, **path_free)


def test_build_ring_orders_and_recovers():
    """_BuildRing unit contract: results re-serialize in submit order
    regardless of completion order; invalidate_after discards
    speculative work; per-task errors surface at their seq; close
    joins the pool."""
    from fast_tffm_tpu.data.pipeline import _BuildRing
    gate = threading.Event()

    def work(_state, payload):
        if payload == "slow":
            gate.wait(5.0)
        if payload == "boom":
            raise ValueError("boom")
        return payload

    ring = _BuildRing(3, depth=8, work=work)
    try:
        s0 = ring.submit("slow")
        s1 = ring.submit("fast1")
        s2 = ring.submit("boom")
        s3 = ring.submit("fast2")
        # Later tasks finish first; wait(s0) must still block until s0.
        deadline = time.monotonic() + 5.0
        while not ring.has(s3) and time.monotonic() < deadline:
            time.sleep(0.01)
        assert ring.has(s1) and ring.has(s3) and not ring.has(s0)
        gate.set()
        assert ring.wait(s0) == ("ok", "slow")
        assert ring.wait(s1) == ("ok", "fast1")
        kind, err = ring.wait(s2)
        assert kind == "error" and isinstance(err, ValueError)
        assert ring.wait(s3) == ("ok", "fast2")
        # Invalidation: queued/unconsumed results past seq are dropped,
        # and new submissions use fresh seqs.
        s4 = ring.submit("a")
        s5 = ring.submit("b")
        ring.wait(s4)
        ring.invalidate_after(s4)
        s6 = ring.submit("c")
        assert s6 > s5
        assert ring.wait(s6) == ("ok", "c")
        assert not ring.has(s5)
    finally:
        ring.close()
    assert all(not t.is_alive() for t in ring._threads)


@needs_cpp
def test_parallel_build_scales(tmp_path):
    """Tier-1 scaling smoke for the parallel plane: its four workers
    build side by side. Asserted as what it means, from the plane's own
    spans, and not as a ratio of two rates (the 1.3x gate of W=4 over
    W=1 measured the host's load: it failed on a quiet sandbox with
    either plane's code, and took turns with its siblings in the
    driver's runs, ROADMAP D13): the seconds inside
    ``pipeline/build_worker`` spans, summed over the ``fm-build-<i>``
    threads, are at least one and a half times the wall they span (3.1
    on a quiet sandbox, 2.4 beside six busy processes: a starved
    scanner leaves workers without a group), every worker has built,
    and the workers' intervals overlap. A plane that serialised (a held
    GIL, one worker fed at a time) reads 1.0 whatever the host's
    load."""
    from fast_tffm_tpu.obs.sink import read_events
    from fast_tffm_tpu.obs.telemetry import RunTelemetry, activate
    rng = np.random.default_rng(0)
    lines = []
    for _ in range(40000):
        ids = rng.choice(100000, size=39, replace=False)
        lines.append("1 " + " ".join(f"{j}:1.5" for j in ids))
    path = tmp_path / "big.txt"
    path.write_text(("\n".join(lines) + "\n") * 3)
    cfg = FmConfig(vocabulary_size=100000, batch_size=8192,
                   train_files=(str(path),), shuffle=True,
                   max_features_per_example=48, bucket_ladder=(48,),
                   host_threads=4)
    tel = RunTelemetry(str(tmp_path / "m.jsonl"), meta={}, trace_spans=True)
    with activate(tel):
        n = sum(b.num_real for b in batch_iterator(
            cfg, cfg.train_files, training=True, epochs=1))
    tel.close()
    assert n == 3 * len(lines)
    spans = [e for e in read_events(str(tmp_path / "m.jsonl"))
             if e["event"] == "span" and e["name"] == "pipeline/build_worker"]
    assert len(spans) == 15     # 120,000 lines in batches of 8192
    by_worker = {}
    for e in spans:
        by_worker.setdefault(e["tid"], []).append((e["ts"], e["ts"] + e["dur"]))
    assert sorted(by_worker) == [f"fm-build-{i}" for i in range(4)]
    wall = max(b for _, b in sum(by_worker.values(), [])) - min(
        a for a, _ in sum(by_worker.values(), []))
    busy = sum(e["dur"] for e in spans)
    assert busy >= 1.5 * wall, (
        f"{busy:.3f} s inside build spans over {wall:.3f} s of wall: "
        f"{busy / wall:.2f} workers building on average, of 4")
    lo = [min(a for a, _ in v) for v in by_worker.values()]
    hi = [max(b for _, b in v) for v in by_worker.values()]
    assert max(lo) < min(hi)
