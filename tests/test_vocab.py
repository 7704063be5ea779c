"""Unbounded-vocabulary admission (fast_tffm_tpu/vocab/; README
"Unbounded vocabulary"): count-min sketch properties, the remap seam's
batch invariants, barrier admission/eviction determinism, sidecar
payload round-trips, the fixed-mode parity pin, and the acceptance
run — admit-mode AUC strictly beats plain modulo collisions on a
heavy-tailed corpus whose distinct-id count exceeds the table 10x.
"""

import dataclasses
import gzip
import json
import os
import re
from types import SimpleNamespace

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.vocab.sketch import HASH_SPACE, CountMinSketch
from fast_tffm_tpu.vocab.table import (COLD_ROW, RESET_CHUNK, VocabMap,
                                       VocabRuntime, payload_crc_ok)


# --- sketch properties ---------------------------------------------------


def test_sketch_no_false_negative():
    """The count-min estimate NEVER undercounts: an id observed k times
    estimates >= k, whatever else collided into its cells."""
    sk = CountMinSketch(width=256, depth=4)
    rng = np.random.default_rng(0)
    truth = {}
    for _ in range(50):
        ids = rng.integers(0, HASH_SPACE, size=rng.integers(1, 40))
        sk.observe(np.unique(ids))
        for i in np.unique(ids).tolist():
            truth[i] = truth.get(i, 0) + 1
    keys = np.fromiter(truth.keys(), np.int64, len(truth))
    est = sk.estimate(keys)
    true = np.asarray([truth[int(k)] for k in keys], np.float32)
    assert (est >= true).all(), "count-min undercounted an observed id"


def test_sketch_bounded_overestimate():
    """Overestimate is bounded by colliding mass per row (~n/width in
    expectation) — pinned empirically on a fixed id set (the hashing is
    constant-multiplier, so this is deterministic) — and shrinks as the
    configured width grows."""
    rng = np.random.default_rng(7)
    ids = np.unique(rng.integers(0, 1 << 30, size=2000))
    over = {}
    for w in (1024, 4096):
        sk = CountMinSketch(width=w, depth=4)
        sk.observe(ids)
        over[w] = sk.estimate(ids) - 1.0
        assert (over[w] >= 0).all()
    assert over[1024].max() <= 6.0, over[1024].max()
    assert over[1024].mean() <= 1.0, over[1024].mean()
    assert over[4096].sum() < over[1024].sum(), (
        "4x the width did not reduce total overestimate")


def test_sketch_decay_monotone():
    """No estimate ever grows from a decay; factor 1.0 is a no-op;
    out-of-range factors are rejected."""
    sk = CountMinSketch(width=128, depth=2)
    ids = np.arange(50, dtype=np.int64) * 977 + 13
    sk.observe(ids, count=4.0)
    before = sk.estimate(ids)
    sk.decay(1.0)
    assert (sk.estimate(ids) == before).all()
    for _ in range(5):
        prev = sk.estimate(ids)
        sk.decay(0.5)
        cur = sk.estimate(ids)
        assert (cur <= prev).all()
        assert (cur == prev * np.float32(0.5)).all()
    for bad in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError):
            sk.decay(bad)


def test_sketch_state_round_trip_exact():
    """state() -> from_state() is bit-exact — including through a JSON
    encode/decode, which is how the payload actually travels inside the
    vocab-<step>.json.gz sidecar."""
    sk = CountMinSketch(width=64, depth=3)
    sk.observe(np.array([3, 99, HASH_SPACE - 1], np.int64), count=2.5)
    sk.decay(0.7)
    state = json.loads(json.dumps(sk.state()))
    back = CountMinSketch.from_state(state)
    assert back.width == sk.width and back.depth == sk.depth
    assert back.counts.tobytes() == sk.counts.tobytes()


def test_sketch_constructor_bounds():
    with pytest.raises(ValueError):
        CountMinSketch(width=32)
    with pytest.raises(ValueError):
        CountMinSketch(width=64, depth=0)
    with pytest.raises(ValueError):
        CountMinSketch(width=64, depth=7)
    assert CountMinSketch.from_mb(1.0, depth=4).width == (1 << 20) // 16


# --- slot map / remap seam -----------------------------------------------


def _runtime(capacity=8, threshold=2.0, decay=0.5):
    return VocabRuntime(capacity, pad_id=capacity, threshold=threshold,
                        decay=decay, sketch=CountMinSketch(width=256))


def _admit(rt, ids, batches=4):
    """Observe ``ids`` in ``batches`` stepped batches, then barrier."""
    ids = np.asarray(ids, np.int64)
    for _ in range(batches):
        rt.note_trained(SimpleNamespace(vocab_obs=ids))
    return rt.barrier(None)


def _decay_barrier(rt, reset_rows=None):
    """A REAL barrier (one decay tick) without touching the ids under
    test: barriers with nothing trained behind them are no-ops (the
    stream is the clock), so aging out an id takes a throwaway
    observation per tick — exactly what a live stream provides."""
    rt.note_trained(SimpleNamespace(
        vocab_obs=np.array([999_999_937], np.int64)))
    return rt.barrier(reset_rows)


def test_lookup_cold_until_admitted():
    rt = _runtime()
    ids = np.array([11, 22, 33], np.int64)
    assert (rt.lookup(ids) == COLD_ROW).all()
    st = _admit(rt, ids)
    assert st["admitted"] == 3 and st["live"] == 3
    rows = rt.lookup(ids)
    assert len(set(rows.tolist())) == 3
    assert (rows >= 1).all() and (rows < 8).all()
    # Unseen ids and the hash-space pad sentinel keep their routes.
    assert rt.lookup(np.array([44], np.int64))[0] == COLD_ROW
    assert rt.lookup(np.array([HASH_SPACE], np.int64))[0] == rt.pad_id


def test_remap_host_dedup_invariants():
    """The remap seam's contract on host-deduped batches: same shapes,
    uniq slots unique among real rows, pad fill holds pad_id, the last
    slot is padding, and every cell still routes to its id's row."""
    rt = _runtime(capacity=8)
    _admit(rt, [100, 200])
    # uniq: 2 admitted, 2 unadmitted (collapse to one cold slot), pad x2
    orig_uniq = np.array([100, 300, 200, 400, HASH_SPACE, HASH_SPACE],
                         np.int64)
    local_idx = np.array([[0, 1, 4], [2, 3, 4]], np.int32)
    batch = SimpleNamespace(uniq_ids=orig_uniq.copy(),
                            local_idx=local_idx.copy())
    out = rt.remap(batch)
    assert out is batch
    assert batch.uniq_ids.shape == orig_uniq.shape
    assert batch.local_idx.shape == local_idx.shape
    real = batch.uniq_ids != rt.pad_id
    assert len(np.unique(batch.uniq_ids[real])) == int(real.sum())
    assert batch.uniq_ids[-1] == rt.pad_id, "last slot must stay padding"
    # Cell-level routing equals the scalar lookup of the original ids.
    want = rt.lookup(orig_uniq[local_idx])
    got = batch.uniq_ids[batch.local_idx]
    assert (got == want).all()
    # vocab_obs carries the distinct REAL hashed ids for note_trained.
    assert sorted(batch.vocab_obs.tolist()) == [100, 200, 300, 400]


@pytest.mark.parametrize("slots,want", [(192, 256), (320, 512), (512, 512)])
def test_remap_for_a_mesh_keeps_the_doubling_rung(slots, want):
    """Under admit a mesh train step's batch is built in the hashed
    space with no row shards in sight, so at one device's
    quarter-octave rung; the remap then orders the physical rows by
    owning shard at the MESH's doubling rung over it (ISSUE 36)."""
    from fast_tffm_tpu.data.pipeline import RowShards
    rt = _runtime(capacity=8)
    _admit(rt, [100, 200])
    rt.row_shards = RowShards(4, 4, rt.pad_id)
    orig_uniq = np.full(slots, HASH_SPACE, np.int64)
    orig_uniq[:4] = [100, 300, 200, 400]
    local_idx = np.array([[0, 1, 4], [2, 3, slots - 1]], np.int32)
    batch = SimpleNamespace(uniq_ids=orig_uniq.copy(),
                            local_idx=local_idx.copy())
    rt.remap(batch)
    assert len(batch.uniq_ids) == want and batch.row_shards == 4
    assert batch.uniq_ids[-1] == rt.pad_id
    segs = batch.uniq_ids.reshape(4, -1)
    for s, seg in enumerate(segs):
        real = seg[seg != rt.pad_id]
        assert (real // 4 == s).all()
    assert (batch.uniq_ids[batch.local_idx]
            == rt.lookup(orig_uniq[local_idx])).all()


def test_remap_raw_ids_batch():
    """dedup=device batches (uniq_ids None) remap cellwise."""
    rt = _runtime(capacity=8)
    _admit(rt, [7])
    cells = np.array([[7, 5, HASH_SPACE]], np.int64)
    batch = SimpleNamespace(uniq_ids=None, local_idx=cells.copy())
    rt.remap(batch)
    assert batch.local_idx[0, 0] == rt.lookup(np.array([7]))[0] != COLD_ROW
    assert batch.local_idx[0, 1] == COLD_ROW
    assert batch.local_idx[0, 2] == rt.pad_id
    assert sorted(batch.vocab_obs.tolist()) == [5, 7]


def test_barrier_admits_at_documented_threshold():
    """An id appearing in EXACTLY vocab_admit_threshold batches is
    admitted at the next barrier: the re-check compares against the
    decay-scaled floor, so the barrier's own decay doesn't silently
    raise the effective admission rate to threshold/decay."""
    for decay in (0.25, 0.5, 1.0):
        rt = _runtime(capacity=8, threshold=2.0, decay=decay)
        st = _admit(rt, [70, 80], batches=2)  # the documented floor
        assert st["admitted"] == 2, (decay, st)


def test_barrier_admits_hottest_first_and_bounds_table():
    """More threshold-crossing candidates than rows: the hottest win,
    the table never exceeds capacity - 1 live rows."""
    rt = _runtime(capacity=4)  # 3 live rows
    hot = np.array([1, 2, 3], np.int64)
    warm = np.array([4, 5], np.int64)
    for _ in range(5):
        rt.note_trained(SimpleNamespace(vocab_obs=hot))
    for _ in range(2):
        rt.note_trained(SimpleNamespace(vocab_obs=warm))
    st = rt.barrier(None)
    assert st["admitted"] == 3 and st["free"] == 0
    assert rt.live_rows == 3
    assert (rt.lookup(hot) != COLD_ROW).all()
    assert (rt.lookup(warm) == COLD_ROW).all()


def test_barrier_evicts_decayed_rows_and_resets_them():
    """An id that stops appearing decays below the floor and is
    evicted: its row lands in the reset hook (cold-start), returns to
    the free list, and a later admission reuses it."""
    rt = _runtime(capacity=4, threshold=2.0, decay=0.25)
    _admit(rt, [10, 20], batches=8)  # est 8 -> decayed 2.0, admitted
    assert rt.live_rows == 2
    old_rows = set(rt.lookup(np.array([10, 20], np.int64)).tolist())
    freed = []
    for _ in range(8):
        if not rt.live_rows:
            break
        _decay_barrier(rt, lambda rows: freed.extend(rows.tolist()))
    assert rt.live_rows == 0
    assert set(freed) == old_rows, (freed, old_rows)
    assert (rt.lookup(np.array([10, 20], np.int64)) == COLD_ROW).all()
    st = _admit(rt, [30, 40, 50], batches=8)
    assert st["admitted"] == 3
    new_rows = rt.lookup(np.array([30, 40, 50], np.int64))
    assert old_rows <= set(new_rows.tolist()), "freed rows not reused"


def test_barrier_deterministic_given_stream():
    """Two runtimes fed the identical observation stream freeze the
    identical slot map — the property that makes a checkpoint replay
    land on the same rows."""
    streams = [np.array([5, 6], np.int64), np.array([6, 7, 8], np.int64),
               np.array([5, 8], np.int64)]
    maps = []
    for _ in range(2):
        rt = _runtime(capacity=6)
        for ids in streams:
            rt.note_trained(SimpleNamespace(vocab_obs=ids))
        rt.barrier(None)
        maps.append(rt._frozen)
    assert (maps[0][0] == maps[1][0]).all()
    assert (maps[0][1] == maps[1][1]).all()


def test_candidate_buffer_bounded_and_deduped():
    """An adversarial flood of threshold-crossers can't grow the
    candidate buffer past its cap — and an ever-present hot id queues
    ONCE per interval, not once per batch (duplicates would exhaust
    the cap and spuriously drop late crossers)."""
    rt = _runtime(capacity=4)
    flood = np.arange(1000, dtype=np.int64)
    for _ in range(3):
        rt.note_trained(SimpleNamespace(vocab_obs=flood))
    assert rt._cand_len <= rt._candidate_cap
    rt2 = _runtime(capacity=1024)
    hot = np.arange(5, dtype=np.int64)
    for _ in range(10):
        rt2.note_trained(SimpleNamespace(vocab_obs=hot))
    assert rt2._cand_len == 5, rt2._cand_len  # queued exactly once
    rt2.barrier(None)  # barrier clears the membership set too
    assert not rt2._queued
    # Re-crossing after eviction re-queues (membership is per
    # interval, not per lifetime).
    for _ in range(12):
        if not rt2.live_rows:
            break
        _decay_barrier(rt2)
    assert rt2.live_rows == 0
    for _ in range(10):
        rt2.note_trained(SimpleNamespace(vocab_obs=hot))
    assert rt2._cand_len == 5


# --- payload / durability ------------------------------------------------


def test_payload_round_trip_bit_exact():
    rt = _runtime(capacity=8)
    _admit(rt, [100, 200, 300])
    rt.barrier(None)  # a decay pass too
    payload = rt.state_payload()
    assert payload_crc_ok(payload)
    cfg = FmConfig(vocabulary_size=8, train_files=("x",))
    back = VocabRuntime(8, pad_id=8, threshold=2.0, decay=0.5,
                        sketch=CountMinSketch(width=256))
    back.load(cfg, json.loads(json.dumps(payload)))
    assert back.state_payload() == payload
    ids = np.array([100, 200, 300, 400, HASH_SPACE], np.int64)
    assert (back.lookup(ids) == rt.lookup(ids)).all()


def test_payload_rejects_tampering_and_mismatch():
    rt = _runtime(capacity=8)
    _admit(rt, [100])
    payload = rt.state_payload()
    cfg = FmConfig(vocabulary_size=8, train_files=("x",))
    torn = json.loads(json.dumps(payload))
    torn["state"]["total_admitted"] = 999
    assert not payload_crc_ok(torn)
    with pytest.raises(ValueError, match="crc32"):
        VocabMap.from_payload(cfg, torn)
    wrong = FmConfig(vocabulary_size=16, train_files=("x",))
    with pytest.raises(ValueError, match="vocabulary_size"):
        VocabMap.from_payload(wrong, payload)


def test_vocab_map_is_inference_half():
    """VocabMap.from_payload reproduces the runtime's frozen routing
    without the sketch — what predict/serve load from the sidecar."""
    rt = _runtime(capacity=8)
    _admit(rt, [100, 200])
    cfg = FmConfig(vocabulary_size=8, train_files=("x",))
    vm = VocabMap.from_payload(cfg, rt.state_payload())
    assert vm.live_rows == rt.live_rows
    ids = np.array([100, 150, 200, HASH_SPACE], np.int64)
    # cfg.pad_id is vocabulary_size (8) == the runtime's pad here
    assert (vm.lookup(ids) == rt.lookup(ids)).all()


def test_reset_table_rows_cold_starts_only_the_given_rows():
    import jax.numpy as jnp
    from fast_tffm_tpu.vocab.table import reset_table_rows
    V, D = 10, 3
    table = jnp.asarray(np.arange(V * D, dtype=np.float32).reshape(V, D)
                        + 1.0)
    acc = jnp.full((V, D), 7.0, jnp.float32)
    before = np.asarray(table).copy()
    rows = np.array([2, 5], np.int32)
    table2, acc2 = reset_table_rows(table, acc, rows, pad_row=V - 1,
                                    adagrad_init=0.1)
    t2, a2 = np.asarray(table2), np.asarray(acc2)
    assert (t2[rows] == 0.0).all()
    assert (a2[rows] == np.float32(0.1)).all()
    untouched = [r for r in range(V - 1) if r not in rows.tolist()]
    assert (t2[untouched] == before[untouched]).all()
    assert (a2[untouched] == 7.0).all()
    assert len(rows) < RESET_CHUNK  # exercised the pad-to-chunk path


def test_backend_reset_rows_hook():
    """The lookup backends' half of the eviction seam."""
    from fast_tffm_tpu.lookup import HostOffloadLookup
    cfg = FmConfig(vocabulary_size=16, factor_num=2,
                   train_files=("x",), lookup="host")
    lk = HostOffloadLookup(cfg, seed=0)
    lk.table[:] = 3.0
    lk.acc[:] = 9.0
    lk.reset_rows(np.array([1, 4], np.int32), adagrad_init=0.5)
    assert (lk.table[[1, 4]] == 0.0).all()
    assert (lk.acc[[1, 4]] == np.float32(0.5)).all()
    assert (lk.table[2] == 3.0).all()


# --- fixed-mode parity + the acceptance run ------------------------------


def _write_corpus(path, rng, n_lines, n_tail, informative=8,
                  tail_repeats=10):
    """Heavy-tailed hashed-string corpus: the label is decided by ONE
    of ``informative`` hot ids per line; ``n_tail`` distinct tail ids
    appear ~``tail_repeats`` times each with random labels — pure
    collision noise for a modulo table."""
    tails = [f"tail{i}" for i in range(n_tail)]
    lines = []
    for k in range(n_lines):
        y = k % 2
        hot = f"hot{(k % informative) // 2 * 2 + y}"
        noise = " ".join(
            f"{tails[int(rng.integers(0, n_tail))]}:0.5"
            for _ in range(max(1, tail_repeats * n_tail // n_lines)))
        lines.append(f"{y} {hot}:1 {noise}")
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    return {f"hot{i}" for i in range(informative)} | set(tails)


def _base_cfg(tmp_path, name, **overrides):
    base = dict(
        vocabulary_size=16, factor_num=4, batch_size=32, epoch_num=4,
        learning_rate=0.1, init_value_range=0.01, shuffle=False, seed=3,
        log_steps=0, hash_feature_id=True,
        train_files=(str(tmp_path / "train.txt"),),
        validation_files=(str(tmp_path / "val.txt"),),
        model_file=str(tmp_path / name / "model" / "fm"),
        log_file=str(tmp_path / name / "fm.log"))
    base.update(overrides)
    return FmConfig(**base)


def test_fixed_mode_parity_and_admit_beats_modulo(tmp_path, rng):
    """The PR's two acceptance pins in one corpus:

    1. ``vocab_mode = fixed`` (the default) is BIT-IDENTICAL to the
       pre-vocab pipeline — the batch stream through the public
       batch_iterator equals the unwrapped historical iterator array
       for array, and a fixed-mode train leaves no vocab sidecar.
    2. With distinct hashed ids >= 10x vocabulary_size, admit-mode
       validation AUC strictly beats plain modulo collisions on the
       same corpus, while the slot map stays bounded by the table.
    """
    import dataclasses as dc

    from fast_tffm_tpu.data.pipeline import (_batch_iterator_impl,
                                             batch_iterator)
    from fast_tffm_tpu.train import train

    distinct = _write_corpus(tmp_path / "train.txt", rng, 600, 300)
    _write_corpus(tmp_path / "val.txt", rng, 200, 300)
    assert len(distinct) >= 10 * 16

    # -- parity pin: the wrapper with vocab=None IS the old pipeline --
    pcfg = _base_cfg(tmp_path, "parity")
    new = list(batch_iterator(pcfg, pcfg.train_files, training=True,
                              epochs=1))
    old = list(_batch_iterator_impl(pcfg, pcfg.train_files,
                                    training=True, epochs=1))
    assert len(new) == len(old) and len(new) > 0
    for a, b in zip(new, old):
        assert a.vocab_obs is None
        for f in dataclasses.fields(a):
            va, vb = getattr(a, f.name), getattr(b, f.name)
            if isinstance(va, np.ndarray):
                assert va.dtype == vb.dtype and (va == vb).all(), f.name
            else:
                assert va == vb, f.name

    def final_auc(cfg):
        assert train(cfg) is None or True
        log = open(cfg.log_file).read()
        m = re.findall(r"validation AUC (\d\.\d+)", log)
        assert m, "no validation AUC in the log"
        return float(m[-1])

    fixed_cfg = _base_cfg(tmp_path, "fixed")
    admit_cfg = _base_cfg(tmp_path, "admit", vocab_mode="admit",
                          vocab_admit_threshold=2.0, vocab_decay=0.5,
                          vocab_sketch_mb=0.25)
    auc_fixed = final_auc(fixed_cfg)
    auc_admit = final_auc(admit_cfg)

    # Fixed mode leaves no vocab sidecar; admit mode leaves a
    # crc-covered one, bounded by the table.
    fixed_dir = fixed_cfg.model_file + ".ckpt"
    assert not [n for n in os.listdir(fixed_dir)
                if n.startswith("vocab-")]
    admit_dir = admit_cfg.model_file + ".ckpt"
    sidecars = sorted(n for n in os.listdir(admit_dir)
                      if n.startswith("vocab-"))
    assert sidecars, os.listdir(admit_dir)
    with gzip.open(os.path.join(admit_dir, sidecars[-1]), "rt") as fh:
        payload = json.load(fh)
    assert payload_crc_ok(payload)
    vm = VocabMap.from_payload(admit_cfg, payload)
    assert 0 < vm.live_rows <= 16 - 1

    assert auc_admit > auc_fixed, (
        f"admit AUC {auc_admit} did not beat modulo collisions "
        f"{auc_fixed} with {len(distinct)} distinct ids in a 16-row "
        "table")
    assert auc_admit > 0.9, auc_admit


# `dataclasses` is imported at module scope for fields() above.


def test_ensure_current_remaps_stale_batches():
    """A batch remapped under generation G must be re-routed if a
    barrier moves the slot map before the batch is stepped — otherwise
    its gradients scatter into rows the barrier evicted, reset, or
    reassigned to other ids."""
    rt = _runtime(capacity=4, threshold=2.0, decay=0.25)
    _admit(rt, [10], batches=8)
    orig_uniq = np.array([10, 20, HASH_SPACE], np.int64)
    local_idx = np.array([[0, 1, 2]], np.int32)
    batch = SimpleNamespace(uniq_ids=orig_uniq.copy(),
                            local_idx=local_idx.copy())
    rt.remap(batch)
    # Same generation: one int compare, same object, untouched arrays.
    u_before = batch.uniq_ids
    assert rt.ensure_current(batch) is batch
    assert batch.uniq_ids is u_before
    # Barrier churn: 10 decays out, 30 takes over (and reuses the row).
    for _ in range(8):
        if not rt.live_rows:
            break
        _decay_barrier(rt)
    assert rt.live_rows == 0
    _admit(rt, [30], batches=8)
    assert rt.lookup(np.array([30], np.int64))[0] in (1, 2, 3)
    stale_cells = batch.uniq_ids[batch.local_idx]
    rt.ensure_current(batch)
    fresh_cells = batch.uniq_ids[batch.local_idx]
    want = rt.lookup(orig_uniq[local_idx])
    assert (fresh_cells == want).all()
    # The stale routing really was wrong (id 10's old private row).
    assert (stale_cells != fresh_cells).any()
    assert fresh_cells[0, 0] == COLD_ROW  # 10 is evicted now
    # Raw-ids batches carry their source too.
    raw = SimpleNamespace(uniq_ids=None,
                          local_idx=np.array([[30, 10]], np.int64))
    rt.remap(raw)
    _decay_barrier(rt)  # a real barrier moves the generation
    rt.ensure_current(raw)
    assert raw.vocab_gen == rt.generation


def test_eval_view_shares_routing_without_counting():
    """Validation sweeps remap through a telemetry-silent snapshot so
    held-out tails don't skew the training cold-hit rate."""
    rt = _runtime(capacity=8)
    _admit(rt, [100, 200])
    view = rt.eval_view()
    assert view.count_telemetry is False and rt.count_telemetry is True
    ids = np.array([100, 150, 200, HASH_SPACE], np.int64)
    assert (view.lookup(ids) == rt.lookup(ids)).all()


def test_stream_admit_requires_publish_interval(tmp_path):
    """run_mode = stream + vocab_mode = admit without publishing would
    never run a single barrier — nothing would ever be admitted."""
    with pytest.raises(ValueError, match="publish_interval_seconds"):
        FmConfig(vocabulary_size=16, train_files=(),
                 run_mode="stream", stream_dir=str(tmp_path),
                 vocab_mode="admit",
                 model_file=str(tmp_path / "m" / "fm"))
    # With an interval it is legal.
    FmConfig(vocabulary_size=16, train_files=(), run_mode="stream",
             stream_dir=str(tmp_path), vocab_mode="admit",
             publish_interval_seconds=1.0,
             model_file=str(tmp_path / "m" / "fm"))


def test_fixed_mode_refuses_admit_checkpoint(tmp_path, rng):
    """The loud-failure inverse of admit-without-sidecar: an
    admit-trained checkpoint loaded under vocab_mode = fixed would
    silently gather arbitrary rows — train resume AND predict must
    both refuse."""
    import dataclasses as dc

    from fast_tffm_tpu.predict import predict
    from fast_tffm_tpu.train import train

    _write_corpus(tmp_path / "train.txt", rng, 64, 30)
    admit_cfg = _base_cfg(tmp_path, "m", epoch_num=1,
                          validation_files=(), vocab_mode="admit",
                          vocab_admit_threshold=2.0)
    train(admit_cfg)
    fixed_cfg = dc.replace(admit_cfg, vocab_mode="fixed", epoch_num=2)
    with pytest.raises(ValueError, match="vocab admission sidecar"):
        train(fixed_cfg)
    pcfg = dc.replace(fixed_cfg,
                      predict_files=(str(tmp_path / "train.txt"),),
                      score_path=str(tmp_path / "score"))
    with pytest.raises(ValueError, match="vocab admission sidecar"):
        predict(pcfg)


def test_fresh_admission_over_restored_table_cold_starts_rows(
        tmp_path, rng):
    """A lost/garbled sidecar on an admit-mode resume starts admission
    fresh — and must also cold-start the assignable rows, or newly
    admitted ids would inherit the lost mapping's trained
    embeddings."""
    import dataclasses as dc

    from fast_tffm_tpu.checkpoint import vocab_sidecar_path
    from fast_tffm_tpu.train import train

    _write_corpus(tmp_path / "train.txt", rng, 64, 30)
    cfg = _base_cfg(tmp_path, "m", epoch_num=1, validation_files=(),
                    vocab_mode="admit", vocab_admit_threshold=2.0)
    train(cfg)
    directory = cfg.model_file + ".ckpt"
    sidecars = [n for n in os.listdir(directory)
                if n.startswith("vocab-")]
    assert sidecars
    for n in sidecars:
        os.remove(os.path.join(directory, n))
    train(dc.replace(cfg, epoch_num=2))
    log = open(cfg.log_file).read()
    assert "admission state starts FRESH" in log
    assert re.search(r"cold-started \d+ table rows", log), (
        "fresh admission over a restored table must reset the rows")
