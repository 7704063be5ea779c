"""Lookup-backend seam (lookup.py; BASELINE config #5): the host-offload
backend must be interchangeable with the fused device path — same math,
same checkpoints, same CLI surface — with only storage/gather/apply moved
off-device."""

import textwrap

import numpy as np
import pytest

import run_tffm
from fast_tffm_tpu.config import FmConfig, load_config
from fast_tffm_tpu.data.pipeline import batch_iterator
from fast_tffm_tpu.lookup import (HostOffloadLookup, PinnedHostLookup,
                                  make_offload_backend,
                                  make_offload_train_step, memory_report,
                                  probe_placement_mode)
from fast_tffm_tpu.models.fm import (ModelSpec, batch_args,
                                     init_accumulator, init_table,
                                     make_grad_fn, make_train_step)
from tests.orbax_caps import orbax_supports_partial_restore
from tests.test_e2e import make_dataset

# ISSUE 3 triage: these paths need PyTreeRestore(partial_restore=True)
# (CheckpointState.restore_partial — the table-without-accumulator
# restore). On an orbax without it the feature cannot work at all, so
# skipping is honest; a capable install still runs them.
requires_partial_restore = pytest.mark.skipif(
    not orbax_supports_partial_restore(),
    reason="installed orbax PyTreeRestore lacks partial_restore")


def _cfg(tmp_path, **kw):
    base = dict(vocabulary_size=200, factor_num=4, batch_size=32,
                learning_rate=0.1, factor_lambda=1e-6, bias_lambda=1e-6,
                train_files=(str(tmp_path / "train.txt"),),
                model_file=str(tmp_path / "model" / "fm_model"),
                shuffle=False, epoch_num=2)
    base.update(kw)
    return FmConfig(**base)


def test_deferred_allocation():
    cfg = FmConfig(vocabulary_size=100, factor_num=4)
    lk = HostOffloadLookup(cfg, _init=False)
    assert lk.table is None and lk.acc is None
    with pytest.raises(ValueError, match="shape"):
        lk.load(np.zeros((3, 3), np.float32), np.zeros((3, 3), np.float32))


def test_host_backend_matches_device_step_for_step(tmp_path, rng):
    """N steps through the host backend == N steps through the fused
    device jit, batch for batch (same seam math on both sides)."""
    make_dataset(tmp_path / "train.txt", 200, rng)
    cfg = _cfg(tmp_path)
    spec = ModelSpec.from_config(cfg)

    table = init_table(cfg, cfg.seed)
    acc = init_accumulator(cfg)
    step = make_train_step(spec)

    lk = HostOffloadLookup(cfg, cfg.seed)
    grad_fn = make_grad_fn(spec)

    for batch in batch_iterator(cfg, cfg.train_files, training=True,
                                epochs=1):
        args = batch_args(batch)
        table, acc, loss_d, _ = step(table, acc, **args)
        gathered = lk.gather(args["uniq_ids"])
        loss_h, _, grad = grad_fn(gathered, **args)
        lk.apply_grad(args["uniq_ids"], np.asarray(grad),
                      cfg.learning_rate)
        assert float(loss_d) == pytest.approx(float(loss_h), abs=1e-6)

    np.testing.assert_allclose(lk.table[:cfg.num_rows], np.asarray(table),
                               atol=2e-6)
    np.testing.assert_allclose(lk.acc[:cfg.num_rows], np.asarray(acc),
                               atol=2e-6)


@pytest.fixture
def host_cfg_files(tmp_path, rng):
    train = tmp_path / "train.txt"
    test = tmp_path / "test.txt"
    make_dataset(train, 400, rng)
    labels = make_dataset(test, 120, rng)
    cfg_path = tmp_path / "fm.cfg"
    cfg_path.write_text(textwrap.dedent(f"""
        [General]
        vocabulary_size = 200
        factor_num = 4
        model_file = {tmp_path}/model/fm_model
        lookup = host

        [Train]
        train_files = {train}
        validation_files = {test}
        epoch_num = 4
        batch_size = 32
        learning_rate = 0.1
        log_steps = 50

        [Predict]
        predict_files = {test}
        score_path = {tmp_path}/score
    """))
    return tmp_path, cfg_path, labels


@requires_partial_restore
def test_host_lookup_e2e_cli(host_cfg_files):
    """Full CLI train -> checkpoint -> predict with lookup = host, and
    the scores match a device-backend predict from the same checkpoint."""
    tmp_path, cfg_path, labels = host_cfg_files
    assert run_tffm.main(["train", str(cfg_path)]) == 0
    assert (tmp_path / "model" / "fm_model.ckpt").is_dir()
    assert run_tffm.main(["predict", str(cfg_path)]) == 0
    scores_host = np.loadtxt(tmp_path / "score" / "test.txt.score")
    assert len(scores_host) == 120

    from fast_tffm_tpu.metrics import exact_auc
    assert exact_auc(scores_host, labels) > 0.8

    # Same checkpoint scored through the device backend: identical.
    cfg = load_config(str(cfg_path))
    import dataclasses
    dev_cfg = dataclasses.replace(
        cfg, lookup="device", score_path=str(tmp_path / "score_dev"))
    from fast_tffm_tpu.predict import predict
    predict(dev_cfg)
    scores_dev = np.loadtxt(tmp_path / "score_dev" / "test.txt.score")
    np.testing.assert_allclose(scores_host, scores_dev, atol=1e-5)


def test_host_lookup_resume(host_cfg_files):
    """from_checkpoint restores exactly what training saved, and a second
    train run resumes from it (step counter advances, table moves)."""
    tmp_path, cfg_path, _ = host_cfg_files
    assert run_tffm.main(["train", str(cfg_path)]) == 0
    cfg = load_config(str(cfg_path))
    lk = HostOffloadLookup.from_checkpoint(cfg)
    assert lk.table.shape == (cfg.ckpt_rows, cfg.row_dim)
    assert lk.step > 0
    t1 = lk.table.copy()

    assert run_tffm.main(["train", str(cfg_path)]) == 0
    lk2 = HostOffloadLookup.from_checkpoint(cfg)
    assert lk2.step > lk.step
    assert not np.array_equal(t1, lk2.table)


@requires_partial_restore
def test_from_checkpoint_table_only(host_cfg_files):
    """with_acc=False (predict) restores just the table leaf: the
    accumulator — half the state at offload scale — never materializes."""
    tmp_path, cfg_path, _ = host_cfg_files
    assert run_tffm.main(["train", str(cfg_path)]) == 0
    cfg = load_config(str(cfg_path))
    full = HostOffloadLookup.from_checkpoint(cfg)
    lean = HostOffloadLookup.from_checkpoint(cfg, with_acc=False)
    assert lean.acc is None
    np.testing.assert_array_equal(lean.table, full.table)
    assert lean.step == full.step


@requires_partial_restore
def test_predict_with_caller_table_stays_host_side(host_cfg_files):
    """predict(cfg, table=...) under lookup=host must wrap the provided
    host table in the backend (for_table), not ship it to a device."""
    tmp_path, cfg_path, _ = host_cfg_files
    assert run_tffm.main(["train", str(cfg_path)]) == 0
    cfg = load_config(str(cfg_path))
    from fast_tffm_tpu.train import train as _train  # table from train()
    import dataclasses
    from fast_tffm_tpu.predict import predict
    table = HostOffloadLookup.from_checkpoint(cfg).table[:cfg.num_rows]
    cfg2 = dataclasses.replace(cfg,
                               score_path=str(tmp_path / "score_t"))
    predict(cfg2, table=table)
    s1 = np.loadtxt(tmp_path / "score_t" / "test.txt.score")
    predict(cfg)  # checkpoint path
    s2 = np.loadtxt(tmp_path / "score" / "test.txt.score")
    np.testing.assert_allclose(s1, s2, atol=1e-6)
    with pytest.raises(ValueError, match="layout"):
        HostOffloadLookup.for_table(cfg, np.zeros((5, 5), np.float32))


def test_placement_probe_resolves_on_cpu():
    """The hermetic CPU platform supports the un-annotated program
    structure ("plain" — device memory IS host RAM there); the chooser
    must therefore pick the in-jit backend."""
    assert probe_placement_mode() == "plain"
    cfg = FmConfig(vocabulary_size=100, factor_num=4)
    lk = make_offload_backend(cfg, seed=0)
    assert isinstance(lk, PinnedHostLookup)
    assert lk.mode == "plain"


def test_pinned_backend_matches_device_step_for_step(tmp_path, rng):
    """N steps through the FUSED in-jit offload program == N steps
    through the fused device jit, batch for batch — the parity contract
    the numpy backend already meets, now for the pinned one (round-3 review,
    next-round #1)."""
    make_dataset(tmp_path / "train.txt", 200, rng)
    cfg = _cfg(tmp_path)
    spec = ModelSpec.from_config(cfg)

    table = init_table(cfg, cfg.seed)
    acc = init_accumulator(cfg)
    step = make_train_step(spec)

    lk = PinnedHostLookup(cfg, cfg.seed)
    off_step = make_offload_train_step(spec, lk, cfg.learning_rate)

    for batch in batch_iterator(cfg, cfg.train_files, training=True,
                                epochs=1):
        args = batch_args(batch)
        table, acc, loss_d, _ = step(table, acc, **args)
        loss_p, _ = off_step(**args)
        assert float(loss_d) == pytest.approx(float(loss_p), abs=1e-6)

    t_p, a_p = (np.asarray(x) for x in lk.state())
    np.testing.assert_allclose(t_p[:cfg.num_rows], np.asarray(table),
                               atol=2e-6)
    np.testing.assert_allclose(a_p[:cfg.num_rows], np.asarray(acc),
                               atol=2e-6)


def test_pinned_seam_methods_match_numpy_backend(tmp_path, rng):
    """gather/apply_grad seam parity: PinnedHostLookup and
    HostOffloadLookup are drop-in interchangeable (same init stream,
    same rows, same post-update state)."""
    make_dataset(tmp_path / "train.txt", 100, rng)
    cfg = _cfg(tmp_path)
    lk_np = HostOffloadLookup(cfg, cfg.seed)
    lk_pin = PinnedHostLookup(cfg, cfg.seed)
    batch = next(batch_iterator(cfg, cfg.train_files, training=True,
                                epochs=1))
    ids = batch.uniq_ids
    np.testing.assert_allclose(np.asarray(lk_pin.gather(ids)),
                               lk_np.gather(ids), atol=1e-7)
    grad = rng.normal(0, 0.1, size=(len(ids), cfg.row_dim)).astype(
        np.float32)
    grad[ids >= cfg.vocabulary_size] = 0.0  # pad rows carry zero grads
    lk_np.apply_grad(ids, grad, cfg.learning_rate)
    lk_pin.apply_grad(ids, grad, cfg.learning_rate)
    np.testing.assert_allclose(np.asarray(lk_pin.table), lk_np.table,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(lk_pin.acc), lk_np.acc,
                               atol=1e-6)


def test_pinned_backend_ffm_fused_step(tmp_path, rng):
    """The fused offload program handles the FFM model family (fields
    threaded through grad_body) — config #3 x config #5 composition."""
    import dataclasses
    from tests.test_e2e import make_dataset as _mk
    lines = []
    for _ in range(64):
        toks = [f"{f}:{int(rng.integers(0, 50))}" for f in range(3)]
        lines.append(" ".join([str(int(rng.integers(0, 2)))] + toks))
    (tmp_path / "train.txt").write_text("\n".join(lines) + "\n")
    cfg = _cfg(tmp_path, vocabulary_size=50, model_type="ffm",
               field_num=3, factor_num=2, batch_size=16)
    spec = ModelSpec.from_config(cfg)
    table = init_table(cfg, cfg.seed)
    acc = init_accumulator(cfg)
    step = make_train_step(spec)
    lk = PinnedHostLookup(cfg, cfg.seed)
    off_step = make_offload_train_step(spec, lk, cfg.learning_rate)
    for batch in batch_iterator(cfg, cfg.train_files, training=True,
                                epochs=1):
        args = batch_args(batch)
        table, acc, loss_d, _ = step(table, acc, **args)
        loss_p, _ = off_step(**args)
        assert float(loss_d) == pytest.approx(float(loss_p), abs=1e-6)


def test_pinned_big_init_layout(monkeypatch):
    """The chunked at-scale init writes uniform rows over [0, vocab),
    keeps the pad row and the ckpt-alignment tail zero, and never
    exceeds init_value_range — checked by forcing the big path at a
    small size."""
    monkeypatch.setattr(HostOffloadLookup, "_DEVICE_INIT_MAX_ROWS", 64)
    cfg = FmConfig(vocabulary_size=300, factor_num=4)
    lk = PinnedHostLookup(cfg, seed=3)
    t = np.asarray(lk.table)
    assert t.shape == (cfg.ckpt_rows, cfg.row_dim)
    live = t[:cfg.vocabulary_size]
    assert np.abs(live).max() <= cfg.init_value_range
    assert (live != 0).mean() > 0.99  # uniform rows actually written
    np.testing.assert_array_equal(t[cfg.vocabulary_size:], 0.0)
    a = np.asarray(lk.acc)
    np.testing.assert_array_equal(a, np.float32(cfg.adagrad_init))


def test_host_lookup_rejects_multiprocess(tmp_path, rng, monkeypatch):
    make_dataset(tmp_path / "train.txt", 50, rng)
    cfg = _cfg(tmp_path, lookup="host")
    import jax
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    from fast_tffm_tpu.train import train
    with pytest.raises(ValueError, match="single-process"):
        train(cfg)


def test_memory_report_keys():
    rep = memory_report()
    assert rep["host_rss_mb"] > 0
