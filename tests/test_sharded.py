"""Mesh-sharded train step == single-device step, bit-for-bit-ish.

SURVEY.md §4: the JAX analogue of the reference's localhost-PS smoke test
is a fake multi-device CPU mesh. These tests run the same batches through
the unsharded jitted step and the 8-device sharded step (data-parallel,
row-sharded table) and require matching results — the property the
reference *cannot* have (its PS updates are async/racy by design).
"""

import jax
import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.pipeline import RowShards, batch_iterator
from fast_tffm_tpu.models.fm import (ModelSpec, batch_args, init_accumulator,
                                     init_table, make_score_fn,
                                     make_train_step)
from fast_tffm_tpu.parallel.sharded import (init_sharded_state, make_mesh,
                                            make_sharded_score_fn,
                                            make_sharded_train_step,
                                            shard_batch)


def _write_data(tmp_path, n=96, seed=3, field_aware=False):
    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(n):
        nnz = rng.integers(1, 12)
        ids = rng.choice(64, size=nnz, replace=False)
        parts = ["1" if rng.random() < 0.5 else "0"]
        for fid in ids:
            if field_aware:
                parts.append(f"{rng.integers(0, 4)}:{fid}:{rng.random():.3f}")
            else:
                parts.append(f"{fid}:{rng.random():.3f}")
        lines.append(" ".join(parts))
    p = tmp_path / "train.txt"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def _mesh_batches(cfg, mesh, **kw):
    """The batches a mesh train step is fed: unique rows ordered by
    owning row shard (data/pipeline.segment_slots)."""
    kw.setdefault("training", True)
    return batch_iterator(
        cfg, cfg.train_files,
        row_shards=RowShards.of(cfg, int(mesh.devices.size)), **kw)


def _cfg(path, **kw):
    base = dict(vocabulary_size=64, factor_num=4, batch_size=16,
                train_files=(path,), epoch_num=1, shuffle=False,
                learning_rate=0.1, factor_lambda=1e-4, bias_lambda=1e-4)
    base.update(kw)
    return FmConfig(**base)


@pytest.mark.parametrize("model_axis", [1, 2])
def test_sharded_step_matches_single_device(tmp_path, model_axis):
    path = _write_data(tmp_path)
    cfg = _cfg(path)
    spec = ModelSpec.from_config(cfg)
    mesh = make_mesh(jax.devices()[:8], model_axis=model_axis)

    table_s, acc_s = init_sharded_state(cfg, mesh, seed=0)
    # Same seed, same init values on the single-device path (sharded table
    # may carry dead pad rows past num_rows for divisibility).
    table_1 = init_table(cfg, 0)
    acc_1 = init_accumulator(cfg)
    np.testing.assert_allclose(np.asarray(table_s)[:cfg.num_rows],
                               np.asarray(table_1), rtol=0, atol=0)

    step_1 = make_train_step(spec)
    step_s = make_sharded_train_step(spec, mesh)
    for batch in _mesh_batches(cfg, mesh):
        args = batch_args(batch)
        table_1, acc_1, loss_1, scores_1 = step_1(table_1, acc_1, **args)
        placed = shard_batch(mesh, **args)
        table_s, acc_s, loss_s, scores_s = step_s(table_s, acc_s, **placed)
        np.testing.assert_allclose(float(loss_s), float(loss_1),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(scores_s),
                                   np.asarray(scores_1),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(table_s)[:cfg.num_rows],
                               np.asarray(table_1), rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(acc_s)[:cfg.num_rows],
                               np.asarray(acc_1), rtol=1e-4, atol=1e-6)


def test_sharded_score_matches(tmp_path):
    path = _write_data(tmp_path, seed=5)
    cfg = _cfg(path)
    spec = ModelSpec.from_config(cfg)
    mesh = make_mesh(jax.devices()[:4])
    table = init_table(cfg, 1)
    table_s, _ = init_sharded_state(cfg, mesh, seed=1)  # same values + pad
    score_1 = make_score_fn(spec)
    score_s = make_sharded_score_fn(spec, mesh)
    for batch in batch_iterator(cfg, cfg.train_files, training=False):
        args = batch_args(batch)
        args.pop("labels"), args.pop("weights")
        s1 = np.asarray(score_1(table, **args))
        ss = np.asarray(score_s(table_s, **shard_batch(mesh, **args)))
        np.testing.assert_allclose(ss, s1, rtol=1e-4, atol=1e-5)


def test_sharded_ffm_step(tmp_path):
    path = _write_data(tmp_path, seed=7, field_aware=True)
    cfg = _cfg(path, model_type="ffm", field_num=4)
    spec = ModelSpec.from_config(cfg)
    mesh = make_mesh(jax.devices()[:8], model_axis=2)
    table_1 = init_table(cfg, 0)
    acc_1 = init_accumulator(cfg)
    table_s, acc_s = init_sharded_state(cfg, mesh, seed=0)
    step_1 = make_train_step(spec)
    step_s = make_sharded_train_step(spec, mesh)
    for batch in _mesh_batches(cfg, mesh):
        args = batch_args(batch)
        table_1, acc_1, loss_1, _ = step_1(table_1, acc_1, **args)
        placed = shard_batch(mesh, **args)
        table_s, acc_s, loss_s, _ = step_s(table_s, acc_s, **placed)
        np.testing.assert_allclose(float(loss_s), float(loss_1),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(table_s)[:cfg.num_rows],
                               np.asarray(table_1), rtol=1e-4, atol=1e-6)


def test_ladder_overflow_stays_power_of_two(tmp_path):
    """The uniq ladder's top rung must stay a power of two so the U axis
    always divides the data axis even when every id is distinct."""
    path = _write_data(tmp_path, n=16, seed=9)
    cfg = _cfg(path, batch_size=16, max_features_per_example=8,
               bucket_ladder=(8,))
    spec = ModelSpec.from_config(cfg)
    mesh = make_mesh(jax.devices()[:8])
    table_s, acc_s = init_sharded_state(cfg, mesh)
    step_s = make_sharded_train_step(spec, mesh)
    loss = None
    for batch in _mesh_batches(cfg, mesh):
        assert len(batch.uniq_ids) % 8 == 0
        args = batch_args(batch)
        table_s, acc_s, loss, _ = step_s(table_s, acc_s,
                                         **shard_batch(mesh, **args))
    assert np.isfinite(float(loss))


def test_shard_batch_rejects_indivisible_batch(tmp_path):
    path = _write_data(tmp_path, n=10, seed=11)
    cfg = _cfg(path, batch_size=10)
    mesh = make_mesh(jax.devices()[:8])
    for batch in batch_iterator(cfg, cfg.train_files, training=True):
        with pytest.raises(ValueError, match="divisible"):
            shard_batch(mesh, **batch_args(batch))
        break


def test_export_npz_slices_padded_table(tmp_path):
    from fast_tffm_tpu.checkpoint import export_npz
    cfg = _cfg(str(tmp_path / "unused.txt"))
    mesh = make_mesh(jax.devices()[:8])
    table_s, _ = init_sharded_state(cfg, mesh)
    assert np.asarray(table_s).shape[0] % 8 == 0  # padded for the mesh
    out = tmp_path / "table.npz"
    export_npz(table_s, str(out), vocabulary_size=cfg.vocabulary_size)
    arr = np.load(out)["table"]
    assert arr.shape == (cfg.vocabulary_size, cfg.row_dim)
    np.testing.assert_allclose(
        arr, np.asarray(table_s)[:cfg.vocabulary_size])


def test_sharded_predict_roundtrip(tmp_path):
    """Mesh-train to a checkpoint, then mesh-predict from it: the table
    restores ROW-SHARDED (each device holds 1/8 of the rows — never
    densified on one device, the config-#5 scaling requirement) and the
    scores match single-device scoring of the same checkpoint."""
    from fast_tffm_tpu.predict import load_table, predict, predict_scores
    from fast_tffm_tpu.train import train
    path = _write_data(tmp_path, n=96, seed=17)
    cfg = _cfg(path, epoch_num=2, model_file=str(tmp_path / "m" / "fm"),
               predict_files=(path,), score_path=str(tmp_path / "score"))
    train(cfg)

    mesh = make_mesh()
    table_s = load_table(cfg, mesh)
    assert int(table_s.shape[0]) == cfg.ckpt_rows
    shard_rows = {s.data.shape[0] for s in table_s.addressable_shards}
    assert shard_rows == {cfg.ckpt_rows // 8}, shard_rows

    raw_s = predict_scores(cfg, table_s, [path], mesh=mesh)
    raw_1 = predict_scores(cfg, load_table(cfg), [path])
    np.testing.assert_allclose(raw_s, raw_1, rtol=1e-4, atol=1e-5)

    written = predict(cfg)  # the driver path picks the mesh itself
    scores = np.loadtxt(written[0])
    assert len(scores) == 96
    np.testing.assert_allclose(
        scores, 1.0 / (1.0 + np.exp(-raw_1)), rtol=1e-3, atol=1e-4)


def test_pallas_kernel_on_mesh_matches_xla(tmp_path):
    """kernel='pallas' survives the sharded jit (the kernel runs under
    shard_map over the data axis — GSPMD cannot partition a pallas_call
    itself) and produces the same step as the XLA scorer: same loss,
    same scores, same updated table, on the 8-device mesh."""
    path = _write_data(tmp_path, n=16, seed=13)
    mesh = make_mesh(jax.devices()[:8])
    results = {}
    for kernel in ("pallas", "xla"):
        cfg = _cfg(path, batch_size=16, kernel=kernel)
        spec = ModelSpec.from_config(cfg)
        table_s, acc_s = init_sharded_state(cfg, mesh)
        step_s = make_sharded_train_step(spec, mesh)
        for batch in _mesh_batches(cfg, mesh):
            table_s, acc_s, loss, scores = step_s(
                table_s, acc_s, **shard_batch(mesh, **batch_args(batch)))
        results[kernel] = (float(loss), np.asarray(scores),
                           np.asarray(table_s))
    loss_p, scores_p, table_p = results["pallas"]
    loss_x, scores_x, table_x = results["xla"]
    assert np.isfinite(loss_p)
    np.testing.assert_allclose(loss_p, loss_x, rtol=1e-5)
    np.testing.assert_allclose(scores_p, scores_x, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(table_p, table_x, rtol=1e-4, atol=1e-7)


def test_sharded_order3_step_matches_single_device(tmp_path):
    """Order-3 ANOVA-kernel FM (BASELINE config #4) under the mesh: the
    lax.scan interaction partitions like the order-2 einsum — sharded
    losses and updated table match the single-device step."""
    path = _write_data(tmp_path, seed=7)
    cfg = _cfg(path, order=3)
    spec = ModelSpec.from_config(cfg)
    mesh = make_mesh(jax.devices()[:8])

    table_s, acc_s = init_sharded_state(cfg, mesh, seed=0)
    table_1, acc_1 = init_table(cfg, 0), init_accumulator(cfg)
    step_1 = make_train_step(spec)
    step_s = make_sharded_train_step(spec, mesh)
    for batch in _mesh_batches(cfg, mesh):
        args = batch_args(batch)
        table_1, acc_1, loss_1, _ = step_1(table_1, acc_1, **args)
        table_s, acc_s, loss_s, _ = step_s(table_s, acc_s,
                                           **shard_batch(mesh, **args))
        np.testing.assert_allclose(float(loss_s), float(loss_1),
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(table_s)[:cfg.num_rows],
                               np.asarray(table_1), rtol=1e-4, atol=1e-6)


# ---- the mesh step on a global batch against the plain reference -------
# (ISSUE 27: four devices, mesh (4,1), as the cell fm16x4-train-zipf)

def _global_batches(cfg, rng, n_batches, hot_rows):
    """Seeded global batches whose ids crowd the table's first rows:
    shard 0 of four is hot, shard 1 holds the rest and the live pad
    row, shards 2 and 3 hold nothing but the dead tail past it."""
    lines = []
    for _ in range(n_batches * cfg.batch_size):
        nnz = int(rng.integers(2, 7))
        hot = rng.choice(hot_rows, size=nnz - 1, replace=False)
        cold = rng.integers(hot_rows, cfg.vocabulary_size)
        ids = np.concatenate([hot, [cold]])
        feats = " ".join(f"{i}:{rng.random() + 0.1:.3f}" for i in ids)
        lines.append(f"{int(rng.random() < 0.4)} {feats}")
    return lines


def _oracle_examples(batch):
    uniq = np.asarray(batch.uniq_ids, np.int64)
    out = []
    for idx, vals in zip(batch.local_idx, batch.vals):
        keep = np.asarray(vals) != 0
        out.append((uniq[np.asarray(idx)[keep]],
                    np.asarray(vals, np.float64)[keep]))
    return out


def test_mesh_step_on_a_global_batch_matches_the_float64_oracle(tmp_path):
    """Synchronous updates (the configuration's guarantee): four
    devices on a global batch owe what one device gives on it. Loss,
    first gradient (from the state after one step, as
    benchmarks/check.py reads it), table and accumulator after three
    steps, against models/oracle.py in float64 on seeded weights. The
    1,501 rows are padded to 4,096 and cut in four: the shards are as
    uneven as they can be (hot, lukewarm, two dead)."""
    from fast_tffm_tpu.models import oracle
    rng = np.random.default_rng(27)
    path = tmp_path / "train.txt"
    cfg = _cfg(str(path), vocabulary_size=1500, batch_size=32,
               max_features_per_example=8, bucket_ladder=(8,))
    path.write_text("\n".join(_global_batches(cfg, rng, 3, 40)) + "\n")
    spec = ModelSpec.from_config(cfg)
    mesh = make_mesh(jax.devices()[:4])
    assert dict(mesh.shape) == {"data": 4, "model": 1}
    table_s, acc_s = init_sharded_state(cfg, mesh, seed=5)
    assert table_s.shape == (4096, cfg.row_dim)
    table = np.asarray(table_s, np.float64)[:cfg.num_rows]
    acc = np.asarray(acc_s, np.float64)[:cfg.num_rows]
    step = make_sharded_train_step(spec, mesh)
    n = 0
    for n, batch in enumerate(_mesh_batches(cfg, mesh), 1):
        assert len(batch.uniq_ids) % 4 == 0
        examples = _oracle_examples(batch)
        labels = np.asarray(batch.labels, np.float64)
        want_loss = (oracle.logistic_loss(
            oracle.batch_scores(table, examples), labels)
            + oracle.regularization(table, examples, cfg.factor_lambda,
                                    cfg.bias_lambda))
        grad = oracle.grad_fd(table, examples, labels, cfg.factor_lambda,
                              cfg.bias_lambda)
        before = table
        table, acc = oracle.adagrad_step(table, acc, grad,
                                         cfg.learning_rate)
        table_s, acc_s, loss, _ = step(
            table_s, acc_s, **shard_batch(mesh, **batch_args(batch)))
        assert float(loss) == pytest.approx(want_loss, rel=2e-6)
        if n == 1:
            got = ((before - np.asarray(table_s, np.float64)[:cfg.num_rows])
                   * np.sqrt(np.asarray(acc_s, np.float64)[:cfg.num_rows])
                   / cfg.learning_rate)
            np.testing.assert_allclose(got, grad, rtol=2e-4, atol=2e-8)
    assert n == 3
    np.testing.assert_allclose(np.asarray(table_s)[:cfg.num_rows], table,
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(acc_s)[:cfg.num_rows], acc,
                               rtol=1e-5, atol=1e-9)
    # the dead tail past the pad row is as it was made
    assert not np.asarray(table_s)[cfg.num_rows - 1:].any()


def test_each_shards_rows_add_up_to_the_uncut_gather(tmp_path):
    """The share tied to the whole: what each shard gathers from its
    own rows for ITS SEGMENT of ``uniq_ids`` (ISSUE 33: the feed is
    ordered by owning shard, so a shard walks U / 4 slots and masks
    nothing), laid side by side, is the gather from the uncut table.
    The host unique as the builder gives it is in first-seen order,
    pad slot first, NOT sorted: that feed's slots of one shard are no
    contiguous range, which is why the data plane orders them."""
    rng = np.random.default_rng(28)
    path = tmp_path / "train.txt"
    cfg = _cfg(str(path), vocabulary_size=1500, batch_size=32,
               max_features_per_example=8, bucket_ladder=(8,))
    path.write_text("\n".join(_global_batches(cfg, rng, 1, 40)) + "\n")
    mesh = make_mesh(jax.devices()[:4])
    table_s, _ = init_sharded_state(cfg, mesh, seed=6)
    first_seen = next(iter(batch_iterator(cfg, cfg.train_files,
                                          training=True)))
    uniq = np.asarray(first_seen.uniq_ids)
    assert uniq[0] == cfg.pad_id and (np.diff(uniq) < 0).any()
    batch = next(iter(_mesh_batches(cfg, mesh)))
    assert batch.row_shards == 4
    uniq = np.asarray(batch.uniq_ids)
    seg = len(uniq) // 4
    whole = np.asarray(table_s)[uniq]
    parts, sizes = [], []
    for s, shard in enumerate(table_s.addressable_shards):
        lo, hi = shard.index[0].start, shard.index[0].stop
        assert (lo, hi) == (1024 * s, 1024 * (s + 1))
        mine = uniq[s * seg:(s + 1) * seg]
        real = mine != cfg.pad_id
        assert ((mine[real] >= lo) & (mine[real] < hi)).all()
        sizes.append(int(real.sum()))
        # a pad slot names the one dead row, which only its own shard
        # holds: the others read zeros for it (the dead row's value)
        local = np.where((mine >= lo) & (mine < hi), mine - lo, 0)
        rows = np.asarray(shard.data)[local]
        parts.append(np.where(((mine >= lo) & (mine < hi))[:, None],
                              rows, 0.0))
    np.testing.assert_array_equal(np.concatenate(parts), whole)
    # real rows: a hot shard, a lukewarm one (which also holds the pad
    # row), and two that own no slot at all; U follows the hot one
    assert sizes[0] > sizes[1] > 0 and sizes[2:] == [0, 0]
    assert seg > sizes[0] >= seg // 2 or seg == 16
    # the same cells name the same rows, example by example
    np.testing.assert_array_equal(
        uniq[batch.local_idx],
        np.asarray(first_seen.uniq_ids)[first_seen.local_idx])


def test_mesh_run_feeds_the_counters_a_reader_needs(tmp_path):
    """ISSUE 27, step 4: on the mesh path ``train/examples`` counts
    the global batch, ``shard_batch`` is timed with the bytes of the
    arrays shipped (since ISSUE 46 by the feed's own thread, as
    ``feed/place`` [``train/place_seconds``]: one process, so every
    batch reaches the loop placed and ``train/h2d`` stays 0), the
    unique counters are fed with the mesh's U, and
    ``train/mesh_devices`` tells the run from a one-device run's
    stream."""
    from fast_tffm_tpu.obs.sink import read_events
    from fast_tffm_tpu.train import train
    rng = np.random.default_rng(29)
    path = tmp_path / "train.txt"
    cfg = _cfg(str(path), vocabulary_size=1500, batch_size=32,
               max_features_per_example=8, bucket_ladder=(8,),
               model_file=str(tmp_path / "m" / "fm"),
               metrics_file=str(tmp_path / "metrics.jsonl"),
               metrics_flush_steps=1, log_steps=0)
    path.write_text("\n".join(_global_batches(cfg, rng, 3, 40)) + "\n")
    train(cfg)
    last = [e for e in read_events(cfg.metrics_file)
            if e.get("event") == "metrics"][-1]
    c, g = last["counters"], last["gauges"]
    assert g["train/mesh_devices"] == jax.device_count() == 8
    assert c["train/steps"] == 3 and c["train/examples"] == 96
    assert c["pipeline/uniq_slots"] % 8 == 0
    assert 0 < c["pipeline/uniq_rows"] < c["pipeline/uniq_slots"]
    # labels, weights, uniq_ids[U], local_idx[B, L], vals[B, L]: 4 B each
    assert c["train/h2d_bytes"] == (
        3 * (32 * 4 * 2 + 2 * 32 * 8 * 4) + 4 * c["pipeline/uniq_slots"])
    assert c["train/place_seconds"] > 0 and c["train/h2d_seconds"] == 0
    assert c["train/placed_ahead"] == c["train/steps"]
