"""End-to-end: config file -> run_tffm.py train -> checkpoint -> predict
-> score files, on a synthetic separable dataset (the reference's
quick-start smoke run, but asserted; SURVEY §4)."""

import os
import textwrap

import numpy as np
import pytest

import run_tffm
from fast_tffm_tpu.config import load_config
from fast_tffm_tpu.metrics import exact_auc


def make_dataset(path, n, rng, vocab=200, informative=6):
    """label=1 examples prefer ids [0, informative), label=0 prefer
    [informative, 2*informative); both share noise ids."""
    lines = []
    labels = []
    for _ in range(n):
        y = int(rng.integers(0, 2))
        base = 0 if y else informative
        feats = {int(base + rng.integers(0, informative)): 1.0,
                 int(base + rng.integers(0, informative)): 1.0}
        for _ in range(3):
            feats[int(rng.integers(2 * informative, vocab))] = round(
                float(rng.uniform(0.5, 1.5)), 3)
        toks = " ".join(f"{i}:{v}" for i, v in sorted(feats.items()))
        lines.append(f"{y} {toks}\n")
        labels.append(y)
    with open(path, "w") as fh:
        fh.writelines(lines)
    return np.array(labels, dtype=np.float64)


@pytest.fixture
def workdir(tmp_path, rng):
    train = tmp_path / "train.txt"
    test = tmp_path / "test.txt"
    make_dataset(train, 600, rng)
    test_labels = make_dataset(test, 200, rng)
    cfg_path = tmp_path / "fm.cfg"
    cfg_path.write_text(textwrap.dedent(f"""
        [General]
        vocabulary_size = 200
        factor_num = 4
        model_file = {tmp_path}/model/fm_model
        log_file = {tmp_path}/log/fm.log

        [Train]
        train_files = {train}
        validation_files = {test}
        epoch_num = 8
        batch_size = 32
        learning_rate = 0.1
        factor_lambda = 1e-6
        bias_lambda = 1e-6
        init_value_range = 0.01
        loss_type = logistic
        log_steps = 50

        [Predict]
        predict_files = {test}
        score_path = {tmp_path}/score
    """))
    return tmp_path, cfg_path, test_labels


def test_train_then_predict_auc(workdir):
    tmp_path, cfg_path, test_labels = workdir
    assert run_tffm.main(["train", str(cfg_path)]) == 0
    # checkpoint + npz exist at the configured model_file
    assert os.path.isdir(f"{tmp_path}/model/fm_model.ckpt")
    assert os.path.exists(f"{tmp_path}/model/fm_model.npz")
    # log file written with step/loss lines
    log = (tmp_path / "log" / "fm.log").read_text()
    assert "loss" in log

    assert run_tffm.main(["predict", str(cfg_path)]) == 0
    score_file = tmp_path / "score" / "test.txt.score"
    scores = np.loadtxt(score_file)
    # one score per input line, order preserving
    assert len(scores) == 200
    assert np.all((scores >= 0) & (scores <= 1))   # sigmoid for logistic
    auc = exact_auc(scores, test_labels)
    assert auc > 0.85, f"e2e AUC too low: {auc}"


def test_resume_from_checkpoint(workdir):
    tmp_path, cfg_path, _ = workdir
    assert run_tffm.main(["train", str(cfg_path)]) == 0
    npz1 = np.load(f"{tmp_path}/model/fm_model.npz")["table"]
    # second run restores and keeps training (step counter advances)
    assert run_tffm.main(["train", str(cfg_path)]) == 0
    log = (tmp_path / "log" / "fm.log").read_text()
    assert "restored checkpoint at step" in log
    npz2 = np.load(f"{tmp_path}/model/fm_model.npz")["table"]
    assert npz1.shape == npz2.shape
    assert not np.array_equal(npz1, npz2)          # it kept learning


def test_interrupted_epoch_schedule_resumes(workdir):
    """A checkpoint recording an incomplete epoch schedule (epoch <
    epoch_num — what a preemption save writes) must resume at the first
    incomplete epoch, not restart the schedule from zero: under
    recurring preemption a from-zero restart would revisit identical
    data and never terminate. A COMPLETED checkpoint keeps the
    reference's train-more semantics (test_resume_from_checkpoint)."""
    from fast_tffm_tpu.checkpoint import CheckpointState
    from fast_tffm_tpu.train import train
    tmp_path, cfg_path, _ = workdir
    cfg = load_config(str(cfg_path))
    assert cfg.epoch_num == 8

    # Run the full schedule once, then rewrite the final checkpoint's
    # metadata to look like a preemption cut it at 5 completed epochs.
    assert run_tffm.main(["train", str(cfg_path)]) == 0
    from fast_tffm_tpu.checkpoint import checkpoint_template
    ckpt = CheckpointState(cfg.model_file)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    steps_full = int(restored["step"])
    steps_per_epoch = steps_full // cfg.epoch_num
    # A save at an existing step is a silent no-op (StepAlreadyExists),
    # so the doctored metadata must land on a NEW step number.
    doctored = steps_full + 1
    ckpt.save(doctored, restored["table"], restored["acc"],
              vocabulary_size=cfg.vocabulary_size, force=True, wait=True,
              epoch=5)
    ckpt.close()

    train(cfg)
    ckpt = CheckpointState(cfg.model_file)
    restored = ckpt.restore(template=checkpoint_template(cfg))
    ckpt.close()
    # Only the 3 incomplete epochs ran (not another full 8)...
    assert int(restored["step"]) == doctored + 3 * steps_per_epoch
    # ...and the finished schedule is recorded as complete.
    assert int(restored["epoch"]) == cfg.epoch_num


def test_predict_without_checkpoint_fails(tmp_path):
    cfg_path = tmp_path / "p.cfg"
    cfg_path.write_text(textwrap.dedent(f"""
        [General]
        vocabulary_size = 10
        model_file = {tmp_path}/model/none
        [Predict]
        predict_files = {tmp_path}/x.txt
        score_path = {tmp_path}/score
    """))
    (tmp_path / "x.txt").write_text("0 1:1\n")
    with pytest.raises(FileNotFoundError):
        run_tffm.main(["predict", str(cfg_path)])


def test_cli_usage_errors():
    assert run_tffm.main([]) == 2
    assert run_tffm.main(["bogus", "x.cfg"]) == 2
