"""ISSUE 33: on a mesh each shard walks the slots of the rows it holds.

(a) the host partition (data/pipeline.segment_slots, as every build
path applies it), (b) the mesh step fed by it against the one-device
step on the same global batch, (c) the lowered mesh program: no gather
or scatter of ``gather`` / ``adagrad`` takes U indices, which is what
keeps the gain from eroding; and the mesh's row-shard check.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data import pipeline
from fast_tffm_tpu.data.pipeline import (RowShards, batch_iterator,
                                         segment_slots)
from fast_tffm_tpu.models.fm import (ModelSpec, batch_args, init_accumulator,
                                     init_table, make_train_step)
from fast_tffm_tpu.parallel import sharded
from fast_tffm_tpu.parallel.sharded import (init_sharded_state, make_mesh,
                                            make_sharded_train_step,
                                            shard_batch)

MESHES = [(4, 1), (2, 2)]
MODELS = ["fm", "ffm"]


def _mesh(shape):
    n_data, n_model = shape
    return make_mesh(jax.devices()[:n_data * n_model], model_axis=n_model)


def _cfg(path, model, **kw):
    base = dict(vocabulary_size=6000, factor_num=4, batch_size=32,
                train_files=(str(path),), epoch_num=1, shuffle=False,
                learning_rate=0.1, factor_lambda=1e-4, bias_lambda=1e-4,
                max_features_per_example=8, bucket_ladder=(8,))
    if model == "ffm":
        base.update(model_type="ffm", field_num=3)
    base.update(kw)
    return FmConfig(**base)


def _write(path, cfg, rng, n_batches, spread):
    """Seeded lines over the first ``spread`` rows: 6,001 rows pad to
    8,192, 2,048 a shard of four, so ``spread`` says how many shards
    hold a row of the batch and how uneven they are."""
    lines = []
    for _ in range(n_batches * cfg.batch_size):
        ids = rng.choice(spread, size=int(rng.integers(2, 8)), replace=False)
        feats = " ".join(
            (f"{int(rng.integers(0, 3))}:" if cfg.model_type == "ffm"
             else "") + f"{i}:{rng.random() + 0.1:.3f}" for i in ids)
        lines.append(f"{int(rng.random() < 0.4)} {feats}")
    path.write_text("\n".join(lines) + "\n")


# ---- (a) the partition -------------------------------------------------

@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("shape", MESHES)
@pytest.mark.parametrize("host_threads", [1, 4])
def test_every_slot_of_a_segment_names_a_row_its_shard_holds(
        tmp_path, shape, model, host_threads):
    """Segment s of ``uniq_ids`` holds rows of shard s and pad slots,
    every row of the batch once, the cells name the rows they named in
    first-seen order, and U is the rung the fullest shard fits."""
    path = tmp_path / "train.txt"
    cfg = _cfg(path, model, host_threads=host_threads)
    _write(path, cfg, np.random.default_rng(33), 3, 6000)
    n = shape[0] * shape[1]
    shards = RowShards.of(cfg, n)
    assert shards == RowShards(n, 8192 // n, 6000)
    plain = list(batch_iterator(cfg, cfg.train_files, training=True))
    cut = list(batch_iterator(cfg, cfg.train_files, training=True,
                              row_shards=shards))
    assert len(plain) == len(cut) == 3
    for a, b in zip(plain, cut):
        assert a.row_shards == 1 and b.row_shards == n
        U = len(b.uniq_ids)
        seg = b.uniq_ids.reshape(n, U // n)
        real = seg != cfg.pad_id
        owner = np.where(real, seg // shards.rows, np.arange(n)[:, None])
        np.testing.assert_array_equal(
            owner, np.broadcast_to(np.arange(n)[:, None], seg.shape))
        # rows lead their segment, pads fill it, the last slot is one
        assert (np.diff(real.astype(int), axis=1) <= 0).all()
        assert not real[:, -1].any()
        rows = np.sort(seg[real])
        np.testing.assert_array_equal(
            rows, np.sort(a.uniq_ids[a.uniq_ids != cfg.pad_id]))
        assert len(rows) == len(set(rows))
        np.testing.assert_array_equal(b.uniq_ids[b.local_idx],
                                      a.uniq_ids[a.local_idx])
        for name in ("labels", "weights", "vals", "fields"):
            np.testing.assert_array_equal(getattr(b, name),
                                          getattr(a, name))
        fullest = int(real.sum(axis=1).max())
        assert U // n > fullest and (U == 64 or U // (2 * n) <= fullest)


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("shape", MESHES)
def test_a_batch_whose_fullest_shard_overflows_takes_the_next_rung(
        tmp_path, shape, model):
    """All the batch's rows lie in the first shard's block: the rows
    fit 192 slots in one list (one device's rung), the first shard's
    would not fit its segment of the mesh's 256, so the batch ships at
    the doubling rung where they do, and nothing is dropped."""
    path = tmp_path / "train.txt"
    cfg = _cfg(path, model)
    _write(path, cfg, np.random.default_rng(34), 1, 2000)
    shards = RowShards.of(cfg, shape[0] * shape[1])
    plain = next(iter(batch_iterator(cfg, cfg.train_files, training=True)))
    cut = next(iter(batch_iterator(cfg, cfg.train_files, training=True,
                                   row_shards=shards)))
    rows = int((plain.uniq_ids != cfg.pad_id).sum())
    assert len(plain.uniq_ids) == 192 and 128 <= rows < 192
    assert len(cut.uniq_ids) == 256 * shards.n
    seg = cut.uniq_ids.reshape(shards.n, -1) != cfg.pad_id
    assert seg.sum(axis=1).tolist() == [rows] + [0] * (shards.n - 1)
    np.testing.assert_array_equal(cut.uniq_ids[cut.local_idx],
                                  plain.uniq_ids[plain.local_idx])


def test_the_generic_builder_and_the_cpp_builder_cut_alike(tmp_path,
                                                          monkeypatch):
    """make_device_batch (no C++ builder, weights, tolerant policies)
    and the builder's emitter give the same segments; only where the
    pad cells point may differ (both at a pad slot)."""
    path = tmp_path / "train.txt"
    cfg = _cfg(path, "fm")
    _write(path, cfg, np.random.default_rng(35), 2, 6000)
    shards = RowShards.of(cfg, 4)
    fast = list(batch_iterator(cfg, cfg.train_files, training=True,
                               row_shards=shards))

    def no_builder(*a, **k):
        raise RuntimeError("forced generic path")
    monkeypatch.setattr(pipeline, "_make_builder", no_builder)
    generic = list(batch_iterator(cfg, cfg.train_files, training=True,
                                  row_shards=shards))
    assert len(fast) == len(generic) == 2
    for a, b in zip(fast, generic):
        assert b.row_shards == 4
        np.testing.assert_array_equal(
            np.sort(a.uniq_ids.reshape(4, -1), axis=1),
            np.sort(b.uniq_ids.reshape(4, -1), axis=1))
        np.testing.assert_array_equal(a.uniq_ids[a.local_idx],
                                      b.uniq_ids[b.local_idx])


def test_a_fixed_bucket_holds_the_fullest_shard_or_says_so():
    """Multi-process feeds pin U: the fullest shard has to fit its
    segment of the bucket, and a batch that does not is an overflow by
    name (the spill protocol's signal), never a row left out."""
    shards = RowShards(4, 2048, 6000)
    uniq = np.arange(100, dtype=np.int32)            # all in shard 0
    idx = np.arange(100, dtype=np.int32).reshape(10, 10)
    fit = functools.partial(pipeline._fit_slots, B=32, L=8,
                            fixed_shape=True, uniq_bucket=512)
    u, li = segment_slots(uniq, idx, shards, fit)
    assert len(u) == 512
    np.testing.assert_array_equal(u[li], uniq[idx])
    with pytest.raises(pipeline.UniqOverflow, match="needs 404 .* 256"):
        segment_slots(uniq, idx, shards, functools.partial(
            pipeline._fit_slots, B=32, L=8, fixed_shape=True,
            uniq_bucket=256))
    with pytest.raises(ValueError, match="do not cut into 4 equal"):
        segment_slots(uniq, idx, shards, lambda need: 1022)


# ---- (b) the step ------------------------------------------------------

@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("shape", MESHES)
def test_three_mesh_steps_agree_with_the_one_device_step(tmp_path, shape,
                                                        model):
    """Loss, table and accumulator after three steps on the same seeded
    global batches, to tests/test_sharded.py's tolerances; and every
    row no example named is bit-equal to what it was, the pad row and
    the dead tail past it among them."""
    path = tmp_path / "train.txt"
    cfg = _cfg(path, model)
    _write(path, cfg, np.random.default_rng(36), 3, 6000)
    spec = ModelSpec.from_config(cfg)
    mesh = _mesh(shape)
    shards = RowShards.of(cfg, 4)
    table_s, acc_s = init_sharded_state(cfg, mesh, seed=3)
    before = np.asarray(table_s), np.asarray(acc_s)
    table_1, acc_1 = init_table(cfg, 3), init_accumulator(cfg)
    step_1, step_s = make_train_step(spec), make_sharded_train_step(spec,
                                                                    mesh)
    named = set()
    for batch in batch_iterator(cfg, cfg.train_files, training=True,
                                row_shards=shards):
        args = batch_args(batch)
        named |= set(batch.uniq_ids[batch.uniq_ids != cfg.pad_id])
        table_1, acc_1, loss_1, scores_1 = step_1(table_1, acc_1, **args)
        table_s, acc_s, loss_s, scores_s = step_s(
            table_s, acc_s, **shard_batch(mesh, **args))
        np.testing.assert_allclose(float(loss_s), float(loss_1),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(scores_s),
                                   np.asarray(scores_1),
                                   rtol=1e-4, atol=1e-5)
    assert 100 < len(named) < cfg.vocabulary_size
    np.testing.assert_allclose(np.asarray(table_s)[:cfg.num_rows],
                               np.asarray(table_1), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(acc_s)[:cfg.num_rows],
                               np.asarray(acc_1), rtol=1e-4, atol=1e-6)
    untouched = np.setdiff1d(np.arange(cfg.ckpt_rows), sorted(named))
    assert cfg.pad_id in untouched
    for now, was in zip((table_s, acc_s), before):
        assert (np.asarray(now)[untouched].tobytes()
                == was[untouched].tobytes())


def test_a_train_run_refuses_a_feed_cut_for_another_mesh(tmp_path,
                                                         monkeypatch):
    """The step cannot see a feed in another order (it would read a
    row outside its shard's segment as zeros); the loop can, from
    ``DeviceBatch.row_shards``."""
    from fast_tffm_tpu import train as train_mod
    path = tmp_path / "train.txt"
    cfg = _cfg(path, "fm", model_file=str(tmp_path / "m" / "fm"),
               log_steps=0)
    _write(path, cfg, np.random.default_rng(37), 1, 6000)
    monkeypatch.setattr(
        train_mod, "EpochFeed",
        lambda *a, row_shards=None, **k: pipeline.EpochFeed(*a, **k))
    with pytest.raises(ValueError, match="1 segment.* 8 row shards"):
        train_mod.train(cfg)


# ---- (c) the lowered program -------------------------------------------

B, L, U = 64, 8, 256


def _lowered(model, shape):
    spec = ModelSpec(
        model_type=model, order=2, factor_num=4,
        field_num=3 if model == "ffm" else 0, vocabulary_size=8191,
        loss_type="logistic", factor_lambda=1e-4, bias_lambda=1e-4,
        learning_rate=0.1, kernel="xla", dedup="host")
    D = spec.row_dim
    args = [jnp.zeros((8192, D)), jnp.ones((8192, D)), jnp.zeros(B),
            jnp.ones(B), jnp.full(U, 8191, jnp.int32),
            jnp.zeros((B, L), jnp.int32), jnp.ones((B, L))]
    if model == "ffm":
        args.append(jnp.zeros((B, L), jnp.int32))
    step = make_sharded_train_step(spec, _mesh(shape))
    return jax.jit(lambda *a: step(*a)).lower(*args).as_text(
        debug_info=True)


def _index_rows(line):
    """Rows of the index operand of a gather or scatter in StableHLO
    text, by their types: ``(operand, indices) -> ...`` for a gather,
    ``(n operands, indices, n updates) -> ...`` for a scatter (n = 2
    for ``adagrad``'s), so the indices are the middle one."""
    types = re.findall(r"tensor<([0-9x]+)x[a-z0-9]+>",
                       line.split(" : ")[-1].split("->")[0])
    assert len(types) in (2, 3, 5), line
    return int(types[len(types) // 2].split("x")[0])


@pytest.mark.parametrize("model", MODELS)
@pytest.mark.parametrize("shape", MESHES)
def test_no_gather_or_scatter_of_the_lookup_walks_all_slots(model, shape):
    """In the lowered mesh step the operations of ``gather`` and
    ``adagrad`` (the table gather; the accumulator's gather and the
    ONE scatter over table and accumulator, ISSUE 38) take U / 4
    indices, never U."""
    lines = _lowered(model, shape).splitlines()
    locs = dict(re.findall(r'(#loc\d+) = loc\("([^"]+)"', "\n".join(lines)))
    seen = {"gather": [], "adagrad": []}
    for i, line in enumerate(lines):
        op = re.search(r'"stablehlo\.(gather|scatter)"\(', line)
        if not op:
            continue
        if op.group(1) == "scatter":
            # a region op: its types and location close the region
            line = next(x for x in lines[i:] if x.lstrip().startswith("})"))
        where = locs[re.findall(r"loc\((#loc\d+)\)", line)[-1]]
        for scope in seen:
            if re.search(rf"(^|/){scope}/", where):
                seen[scope].append((op.group(1), _index_rows(line)))
    assert sorted(seen["gather"]) == [("gather", U // 4)]
    assert sorted(seen["adagrad"]) == [("gather", U // 4),
                                       ("scatter", U // 4)]


# ---- the mesh's row shards against the ladder --------------------------

def test_make_mesh_checks_the_row_shards_against_the_smallest_rung(
        monkeypatch):
    """The segments need the ladder's smallest rung to divide by the
    number of ROW shards, data x model, not by the data axis alone."""
    assert pipeline.UNIQ_LADDER_MIN == pipeline._uniq_ladder(4, 4)[0] == 64
    devices = jax.devices()[:8]
    assert dict(make_mesh(devices, model_axis=2).shape) == {"data": 4,
                                                            "model": 2}
    monkeypatch.setattr(sharded, "UNIQ_LADDER_MIN", 4)
    with pytest.raises(ValueError, match=r"8 row shards \(data axis 4 x "
                       r"model axis 2\).*smallest rung, 4 slots"):
        make_mesh(devices, model_axis=2)
    assert make_mesh(devices[:4], model_axis=2).devices.size == 4


# ---- a fixed bucket on a mesh (multi-process feeds) --------------------

@pytest.mark.parametrize("path_kind", ["cpp", "cpp_workers", "generic"])
def test_a_fixed_bucket_spills_on_the_fullest_shard(tmp_path, monkeypatch,
                                                    path_kind):
    """Every row lies in the first of four shards: a bucket of 256 has
    room for the batch's 160-odd rows in one list, its first segment
    (63 rows and a pad slot) has not, so batches close early on the
    SHARD's budget, every example still ships exactly once, and every
    batch keeps its shape; C++ builder, its workers and the generic
    path alike."""
    path = tmp_path / "train.txt"
    cfg = _cfg(path, "fm", host_threads=4 if path_kind == "cpp_workers"
               else 1)
    _write(path, cfg, np.random.default_rng(38), 3, 2000)
    if path_kind == "generic":
        def no_builder(*a, **k):
            raise RuntimeError("forced generic path")
        monkeypatch.setattr(pipeline, "_make_builder", no_builder)
    shards = RowShards.of(cfg, 4)
    whole = list(batch_iterator(cfg, cfg.train_files, training=True))
    want = [tuple(np.sort(b.uniq_ids[i][v != 0])) + tuple(v[v != 0])
            for b in whole for i, v in zip(b.local_idx[:b.num_real],
                                           b.vals[:b.num_real])]
    stats = pipeline.SpillStats()
    got, batches = [], list(batch_iterator(
        cfg, cfg.train_files, training=True, fixed_shape=True,
        uniq_bucket=256, row_shards=shards, stats=stats))
    for b in batches:
        assert len(b.uniq_ids) == 256 and b.row_shards == 4
        real = b.uniq_ids.reshape(4, 64) != cfg.pad_id
        assert real[0].sum() <= 63 and not real[1:].any()
        got += [tuple(np.sort(b.uniq_ids[i][v != 0])) + tuple(v[v != 0])
                for i, v in zip(b.local_idx[:b.num_real],
                                b.vals[:b.num_real])]
    assert got == want and len(batches) > len(whole) == 3
    assert stats.spilled_batches >= 3
    # what adapt_uniq_bucket reads: the slots the densest batch needs
    assert stats.max_uniq == 4 * max(
        int((b.uniq_ids != cfg.pad_id).sum()) for b in batches)


def test_the_probe_sizes_the_bucket_for_the_fullest_shard(tmp_path):
    """One list of 160-odd rows probes to 512; cut in four with every
    row in the first shard it needs four times that."""
    path = tmp_path / "train.txt"
    cfg = _cfg(path, "fm")
    _write(path, cfg, np.random.default_rng(39), 3, 2000)
    one = pipeline.probe_uniq_bucket(cfg, cfg.train_files)
    four = pipeline.probe_uniq_bucket(cfg, cfg.train_files,
                                      shards=RowShards.of(cfg, 4))
    assert (one, four) == (512, 2048)
    assert pipeline.uniq_bucket_top(cfg, shards=RowShards.of(cfg, 4)) \
        == 4 * pipeline.uniq_bucket_top(cfg)


# ---- the builder's cell count ------------------------------------------

@pytest.mark.parametrize("host_threads", [1, 4])
@pytest.mark.parametrize("cut", [False, True])
def test_the_builders_cell_count_is_the_count_of_real_cells(
        tmp_path, host_threads, cut):
    """``DeviceBatch.nnz`` (what ``pipeline/feature_nnz`` adds up where
    the C++ builder made the batch) is what a pass over the B x L cells
    counts; the generic builder leaves it to the reader."""
    path = tmp_path / "train.txt"
    cfg = _cfg(path, "fm", host_threads=host_threads, batch_size=20)
    _write(path, _cfg(path, "fm"), np.random.default_rng(40), 3, 6000)
    shards = RowShards.of(cfg, 4) if cut else None
    batches = list(batch_iterator(cfg, cfg.train_files, training=True,
                                  row_shards=shards))
    assert len(batches) == 5 and batches[-1].num_real == 16
    for b in batches:
        real = np.take(b.uniq_ids != cfg.pad_id, b.local_idx)
        assert b.nnz == int(real.sum()) == int((b.vals != 0).sum())
    block = pipeline.ParsedBlock(
        labels=np.zeros(1, np.float32), poses=np.array([0, 2], np.int32),
        ids=np.array([3, 4], np.int32), vals=np.ones(2, np.float32))
    assert pipeline.make_device_batch(block, cfg).nnz is None
