"""The cell ``fm16-stream-catchup`` (``kind: train_stream``) at a size
the CPU holds: the repo's own traffic file on a tiny table through
``--rehearse-cpu``, each fault the stream's checks exist for planted
and caught, and ``benchmarks/stream_reference.py`` against the
program's own stream source. Nothing here describes a TPU topology."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import tiny_stream_tree
import tiny_tree
from benchmarks import corpus as corpus_mod, harness, stream_reference
from benchmarks.drivers import train_stream

REPO = tiny_tree.REPO
CELL = "fm16-stream-catchup"
STREAM_CHECKS = list(tiny_stream_tree.STREAM_CHECKS)
EXACT = ["feed_examples_not_in_corpus", "feed_examples_short_of_batch",
         "span_examples_credited_not_counted"] + STREAM_CHECKS
NEW_METRICS = ["stream_pump_s_per_batch", "stream_snapshot_s_per_batch",
               "stream_read_s_per_batch", "stream_starved_share"]
APPENDED_TO = [
    "train_examples_per_s_per_chip", "input_wait_share",
    "h2d_bytes_per_example", "step_device_ms", "step_roofline",
    "steady_rate.train", "table_gather_ms", "slot_expand_ms",
    "interaction_ms", "table_scatter_ms", "step_unscoped_ms",
    "loss_sync_share", "uniq_slot_fill", "host_build_s_per_batch",
    "cell_fill", "loop_h2d_s_per_step", "place_s_per_step",
    "placed_ahead_share", "emit_s_per_batch"]


@pytest.fixture(autouse=True)
def _work_root_of_its_own(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK_ROOT", str(tmp_path / "work"))


@pytest.fixture(scope="module")
def stream_root(tmp_path_factory):
    return tiny_stream_tree.make(str(tmp_path_factory.mktemp("stream_tree")))


def _spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


# ---- the entries and the files ------------------------------------------

def test_the_cell_is_one_chip_on_the_configuration_the_issue_names():
    spec = _spec()
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config="fm-k16-criteo1tb-stream",
                        traffic="stream-catchup", chips=1)
    loaded = harness.load_cell(CELL)
    assert loaded.kind == "train_stream"
    assert loaded.traffic == dict(
        loaded.traffic, corpus_batches=32, corpus_files=4,
        backlog_passes=256, steps_per_reading=8, checked_steps=3,
        checked_stream_batches=160, trace_seconds=5)
    # the warm-up holds every checked batch, and may rise to 64
    assert 20 <= loaded.traffic["warmup_readings"] <= 64
    assert "STOP" not in json.dumps(loaded.traffic)


@pytest.mark.parametrize("name", APPENDED_TO + NEW_METRICS)
def test_the_cell_is_on_the_lists_the_issue_names(name):
    spec = _spec()
    m = next(e for e in spec["end_to_end"] + spec["per_layer"]
             if e["name"] == name)
    if name in NEW_METRICS:
        assert m["workloads"] == [CELL]
        assert m["layer"] == "stream source (data/stream.py)"
        assert m["moves"] == "train_examples_per_s_per_chip"
    else:
        assert m["workloads"][-1] == CELL


def test_the_cell_is_on_no_list_the_issue_keeps_it_off():
    on = {m["name"] for m in _spec()["per_layer"]
          if CELL in m.get("workloads", ())}
    assert on == set(APPENDED_TO[1:] + NEW_METRICS)
    assert not on & {"epoch_barrier_s", "compiles_per_epoch",
                     "dedup_sort_ms", "bookkeeping_s_per_step",
                     "idle_in_first_batch"}


def test_the_configuration_is_the_train_cells_plus_the_stream_settings():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "fm-k16-criteo1tb.json")) as fh:
        base = json.load(fh)
    own = harness.load_cell(CELL).config
    assert own["program"]["General"] == base["program"]["General"]
    train = dict(own["program"]["Train"])
    stream = {k: train.pop(k) for k in (
        "run_mode", "stream_dir", "stream_poll_seconds", "seal_policy",
        "publish_interval_seconds")}
    assert train == base["program"]["Train"]
    assert stream == dict(stream, run_mode="stream", stream_poll_seconds=2,
                          seal_policy="done", publish_interval_seconds=300)
    assert own["features"] == base["features"]
    assert own["reference_family"] == base["reference_family"] == "fm_order2"
    assert own["reduced"] == base["reduced"]
    limits = own["check_limits"]
    assert limits["train"] == base["check_limits"]["train"]
    assert limits["train_stream"] == dict(
        limits["train"], **dict.fromkeys(STREAM_CHECKS, 0))
    assert len(own["guarantees"]) >= 4 and len(own["source"]) <= 200
    entry = next(c for c in _spec()["configs"]
                 if c["name"] == "fm-k16-criteo1tb-stream")
    assert entry["source"] == own["source"]
    assert entry["reduced"] == own["reduced"]


# ---- the cell's own traffic file on a tiny table -------------------------

def _bench(root, *args):
    p = subprocess.run([sys.executable, "-m", "benchmarks.run", *args],
                       cwd=root, env=tiny_tree.env(), capture_output=True,
                       text=True, timeout=600)
    return p.returncode, p.stdout.strip().splitlines(), p.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_the_repos_traffic_file_runs_on_the_tiny_table(stream_root, trace):
    """1,024 sealed shards of 8 batches under the README's stream
    settings, one CPU device (the one-chip path): a result line,
    ``correct``, every exact check 0, ``check`` its last key; traced,
    the per-layer metrics the repo lists for the cell."""
    rc, out, err = _bench(stream_root, "--workload", tiny_stream_tree.CELL,
                          "--seed", str(2 ** 31 + 49), "--seconds", "0.5",
                          "--trace", str(trace), "--rehearse-cpu")
    assert rc == 0, err[-3000:]
    last = json.loads(out[-1])
    assert list(last)[-1] == "check"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 12 and last["metrics"] == {}   # rehearsal
    for name in EXACT:
        assert last["check"][name] == {"value": 0, "limit": 0}, name
    assert {"loss_rel_gap_max", "grad_norm_gap_worst_leaf",
            "update_norm_gap_worst_leaf_3_steps"} <= set(last["check"])
    assert any(l.startswith("stream: ledger of 1024 shards, 131072 lines; "
                            "160 stepped batches") for l in out)
    shown = json.loads(next(l for l in out if l.startswith("metrics: "))
                       [len("metrics: "):])
    if not trace:
        assert set(shown) == {"train_examples_per_s_per_chip", "setup_s"}
        return
    assert "breakdown" in last
    assert set(NEW_METRICS) <= set(shown)
    assert {"input_wait_share", "uniq_slot_fill", "host_build_s_per_batch",
            "cell_fill", "loop_h2d_s_per_step", "place_s_per_step",
            "placed_ahead_share", "emit_s_per_batch", "loss_sync_share",
            "h2d_bytes_per_example", "step_device_ms",
            "steady_rate.train"} <= set(shown)
    # the loop places for itself, the feed's thread is not there
    assert shown["placed_ahead_share"]["value"] == 0
    assert shown["place_s_per_step"]["value"] == 0
    assert shown["loop_h2d_s_per_step"]["value"] > 0
    assert shown["stream_starved_share"]["value"] == 0
    assert (shown["stream_pump_s_per_batch"]["value"]
            > shown["stream_snapshot_s_per_batch"]["value"] > 0)
    assert any(l.startswith("the span's producer thread: ") for l in out)


def test_a_program_without_the_read_planes_names_fails_at_once(
        stream_root, monkeypatch):
    """What the parent commit does with this PR's benchmark files laid
    over it: no result, and no shard written first."""
    from fast_tffm_tpu.data import stream as streamlib
    monkeypatch.delattr(streamlib, "PUMP_LEAVES")
    cell = harness.load_cell(tiny_stream_tree.SHORT, stream_root)
    with pytest.raises(harness.RunFailed, match="PUMP_LEAVES"):
        train_stream.run(_run_for(cell), {"platform": "cpu", "kind": "cpu",
                                          "count": 1})
    assert not os.path.exists(harness.WORK_ROOT)


# ---- each fault planted, each caught -------------------------------------

def _run_for(cell, seconds=0.5):
    return harness.Run(cell=cell, seed=2 ** 31 + 7, seconds=seconds,
                       trace=False, rehearse=True, t0=time.monotonic())


def _shard(stream_dir, k):
    return os.path.join(stream_dir, f"part-{k:06d}.libsvm")


def _point(link, target):
    os.remove(link)
    os.symlink(target, link)


def _left_out(stream_dir):
    """The third shard never reaches the program's ledger."""
    os.remove(_shard(stream_dir, 2))
    os.remove(_shard(stream_dir, 2) + ".done")


def _listed_twice(stream_dir):
    """The second shard's name holds the first shard's lines."""
    _point(_shard(stream_dir, 1), os.readlink(_shard(stream_dir, 0)))


def _swapped(stream_dir):
    """The second and third shards' lines, each under the other's
    name: every line is still trained once."""
    a, b = (os.readlink(_shard(stream_dir, k)) for k in (1, 2))
    _point(_shard(stream_dir, 1), b)
    _point(_shard(stream_dir, 2), a)


def _runs_dry(stream_dir):
    """A backlog of 14 shards, 28 batches: eaten inside the window."""
    for name in sorted(os.listdir(stream_dir))[28:]:
        os.remove(os.path.join(stream_dir, name))


def _half_the_batch(step):
    def broken(*args, **kwargs):
        kwargs = dict(kwargs)
        w = np.asarray(kwargs["weights"]).copy()
        w[: len(w) // 2] = 0
        kwargs["weights"] = w
        return step(*args, **kwargs)
    return broken


_half_the_batch.above_probe = True      # in the data plane


def _one_line_off(monkeypatch):
    """The program tags every batch with one line more than it holds."""
    from fast_tffm_tpu.data import stream as streamlib
    real = streamlib.StreamSource._snapshot

    def snapshot(self):
        payload = real(self)
        for f in payload["files"]:
            if f["lines"]:
                f["lines"] += 1
                break
        return payload
    monkeypatch.setattr(streamlib.StreamSource, "_snapshot", snapshot)


FAULTS = {
    "none": ({}, None),
    # the corpus is listed over and over, so the shard that moves up
    # into the prefix holds the very lines of the one left out: every
    # line is still fed as often as the prefix holds it
    "a shard left out of the ledger": (
        {"after_shards": _left_out},
        ["stream_batches_not_in_ledger_order", "watermark_lines_off"]),
    "a shard listed twice under one name's content": (
        {"after_shards": _listed_twice},
        ["stream_batches_not_in_ledger_order",
         "stream_lines_trained_twice_or_never"]),
    "two shards swapped in order": (
        {"after_shards": _swapped}, ["stream_batches_not_in_ledger_order"]),
    "a watermark one line off": (
        {"program": _one_line_off}, ["watermark_lines_off"]),
    "a backlog that runs dry inside the span": (
        {"after_shards": _runs_dry}, "RunFailed"),
    "half a batch zero-weighted": (
        {"breaker": _half_the_batch},
        ["feed_examples_short_of_batch",
         "stream_batches_not_in_ledger_order",
         "stream_lines_trained_twice_or_never"]),
}
# what a fault may fail besides (the reference follows the feed it was
# given, so half a batch moves the three gaps by the seed)
MAY_FAIL_TOO = {"half a batch zero-weighted": {
    "loss_rel_gap_max", "grad_norm_gap_worst_leaf",
    "update_norm_gap_worst_leaf_3_steps"}}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_each_planted_fault_is_caught(stream_root, fault, monkeypatch):
    """The rest of a run past the look for a chip, in this process
    (eight CPU devices: a one-process stream on the mesh path), with
    the fault planted behind the reference's back. The sound run
    passes the same checks; each fault fails exactly the checks that
    exist for it, or the run."""
    import jax
    kw, caught = FAULTS[fault]
    kw = dict(kw)
    if "program" in kw:
        kw.pop("program")(monkeypatch)
    monkeypatch.setattr(train_stream, "DRY_AFTER_S", 1.0)
    cell = harness.load_cell(tiny_stream_tree.SHORT, stream_root)
    device = {"platform": "cpu", "kind": "cpu", "count": jax.device_count()}
    if caught == "RunFailed":
        with pytest.raises(harness.RunFailed, match="ran dry"):
            train_stream.run(_run_for(cell, seconds=30.0), device, **kw)
        return
    line = json.loads(train_stream.run(_run_for(cell), device, **kw))
    assert line["correct"] is (caught is None)
    failed = [k for k, c in line["check"].items()
              if c["value"] > c["limit"]
              and k not in MAY_FAIL_TOO.get(fault, ())]
    assert failed == (caught or [])


# ---- the reference, alone and against the program's stream source --------

@pytest.mark.parametrize("data,sealed,ends", [
    (b"a\nbc\n", True, [2, 5]), (b"a\nbc\n", False, [2, 5]),
    (b"a\nbc", True, [2, 4]), (b"a\nbc", False, [2]),
    (b"", True, []), (b"abc", False, [])])
def test_line_ends_are_the_lines_a_stream_may_consume(data, sealed, ends):
    assert stream_reference.line_ends(data, sealed).tolist() == ends


def _tiny_reference(sealed_last=True):
    """Three shards of 5, 3 and 4 lines of two bytes, signatures 1..12,
    named out of order; batches of 4."""
    def shard(name, lo, n, sealed=True):
        return stream_reference.Shard(
            name, np.arange(lo, lo + n, dtype=np.uint64),
            2 * np.arange(1, n + 1, dtype=np.int64), sealed)
    return stream_reference.StreamReference(
        [shard("s/b", 6, 3), shard("s/c", 9, 4, sealed_last),
         shard("s/a", 1, 5)], 4)


def test_the_reference_cuts_the_concatenated_ledger_every_batch():
    ref = _tiny_reference()
    assert [s.path for s in ref.ledger] == ["s/a", "s/b", "s/c"]
    assert (ref.lines, ref.batches) == (12, 3)
    assert ref.batch(1).tolist() == [5, 6, 7, 8]        # spans s/a | s/b
    with pytest.raises(IndexError):
        ref.batch(3)
    assert [(w["lines"], w["bytes"]) for w in ref.watermark(2)] == [
        (5, 10), (3, 6), (0, 0)]
    assert [(w["lines"], w["bytes"]) for w in ref.watermark(3)] == [
        (5, 10), (3, 6), (4, 8)]


@pytest.mark.parametrize("fed,order,once", [
    ([[1, 2, 3, 4], [5, 6, 7, 8]], 0, 0),
    ([[5, 6, 7, 8], [1, 2, 3, 4]], 2, 0),           # out of order, all once
    ([[1, 2, 3, 4], [1, 2, 3, 4]], 1, 8),           # four twice, four never
    ([[1, 2, 3, 4], [5, 6, 0, 0]], 1, 2),           # two not trained on
    ([[1, 2, 3, 4], [5, 6, 7, 99]], 1, 2),          # one never, one foreign
])
def test_the_reference_counts_order_and_exactly_once(fed, order, once):
    ref = _tiny_reference()
    fed = [np.asarray(b, dtype=np.uint64) for b in fed]
    assert ref.not_in_ledger_order(fed) == order
    assert ref.twice_or_never(fed) == once


@pytest.mark.parametrize("edit,off", [
    (lambda f: None, 0),
    (lambda f: f[1].update(lines=f[1]["lines"] + 1), 1),
    (lambda f: f[0].update(bytes=f[0]["bytes"] - 2), 2),
    (lambda f: f.pop(), 1),                     # a shard the payload lacks
    (lambda f: f[2].update(path="s/z"), 1),     # under another name
])
def test_the_reference_holds_a_watermark_to_the_line_and_the_byte(edit, off):
    ref = _tiny_reference()
    files = [dict(w) for w in ref.watermark(2)]
    edit(files)
    assert ref.watermark_off({"files": files}, 2) == off
    assert ref.watermark_off(None, 2) > 0


def test_nothing_behind_an_unsealed_shard_is_read():
    ref = stream_reference.StreamReference([
        stream_reference.Shard("s/a", np.arange(1, 4, dtype=np.uint64),
                               np.array([2, 4, 6]), sealed=False),
        stream_reference.Shard("s/b", np.arange(4, 9, dtype=np.uint64),
                               2 * np.arange(1, 6))], 2)
    assert (ref.lines, ref.batches) == (3, 1)
    assert [w["lines"] for w in ref.watermark(5)] == [2, 0]


def test_the_reference_agrees_with_the_programs_stream_source(tmp_path):
    """A seeded three-shard stream, the last shard unsealed with a torn
    last line held back: the program's own source (four build workers)
    yields the reference's batches, signature for signature, and tags
    each with the reference's watermark. The reference sees the sealed
    bytes only: the generator's record and the files."""
    from fast_tffm_tpu.data import cparser, stream as streamlib
    if not cparser.available():
        pytest.skip("C++ extension unavailable")
    conf = tiny_stream_tree.TINY_STREAM
    B, vocab = 16, conf["program"]["General"]["vocabulary_size"]
    c = corpus_mod.generate(conf["features"], "fm", vocab, 150, 49,
                            str(tmp_path / "corpus"), 3, "part")
    sd = tmp_path / "stream"
    sd.mkdir()
    sigs = c.signatures()
    bounds = np.concatenate([[0], np.cumsum(c.lines_per_file)])
    shards = []
    for f, path in enumerate(c.files):
        with open(path, "rb") as fh:
            data = fh.read()
        sealed = f < 2
        if not sealed:
            data = data[:-7]        # the writer is mid-line
        dst = sd / os.path.basename(path)
        dst.write_bytes(data)
        if sealed:
            (sd / (dst.name + ".done")).touch()
        ends = stream_reference.line_ends(data, sealed)
        shards.append(stream_reference.Shard(
            str(dst), sigs[bounds[f]:bounds[f] + len(ends)], ends, sealed))
    ref = stream_reference.StreamReference(shards, B)
    assert ref.lines == 149 and ref.batches == 9
    cfg = harness.program_cfg(conf, {"Train": {
        "stream_dir": str(sd), "host_threads": 4}}, str(tmp_path / "w"))
    tracker = streamlib.StreamTracker(str(sd), 0.01, "done")
    src = streamlib.StreamSource(cfg, tracker, workers=4)
    fed = []
    for _ in range(2000):
        b = src.next_batch(block=False)
        if b is streamlib.IDLE:
            if len(fed) == ref.batches:
                break
            time.sleep(0.005)
            continue
        rows = np.asarray(b.uniq_ids)[np.asarray(b.local_idx)]
        millis = np.rint(np.asarray(b.vals, np.float64) * 1000)
        fed.append(corpus_mod.example_signatures(
            np.asarray(b.labels).astype(np.int64), rows,
            millis.astype(np.int64)))
        assert ref.watermark_off(b.stream_pos, len(fed)) == 0
    src.close()
    assert len(fed) == ref.batches          # 5 whole lines stay unbatched
    assert ref.not_in_ledger_order(fed) == 0
    assert ref.twice_or_never(fed) == 0
