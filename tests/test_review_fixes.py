"""Regression tests for review findings: bucket-ladder overflow, blank-
line alignment in predict, kernel validation, zero-step train runs."""

import textwrap

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.parser import parse_lines
from fast_tffm_tpu.data.pipeline import make_device_batch
from tests.orbax_caps import orbax_enforces_template_shapes


def test_example_longer_than_ladder_gets_pow2_bucket():
    cfg = FmConfig(vocabulary_size=5000, batch_size=2,
                   bucket_ladder=(4, 8), max_features_per_example=0)
    line = "1 " + " ".join(f"{i}:1" for i in range(300))
    block = parse_lines([line], 5000)
    b = make_device_batch(block, cfg)
    assert b.local_idx.shape[1] == 512        # next pow2 above 300
    assert b.num_real == 1


def test_keep_empty_preserves_line_alignment():
    lines = ["1 3:1", "", "0 4:1", "   "]
    block = parse_lines(lines, 10, keep_empty=True)
    assert block.batch_size == 4
    np.testing.assert_array_equal(block.sizes, [1, 0, 1, 0])
    # without keep_empty blanks are dropped (training path)
    assert parse_lines(lines, 10).batch_size == 2


def test_predict_blank_line_scores(tmp_path, rng):
    import run_tffm
    train = tmp_path / "train.txt"
    train.write_text("".join(
        f"{i % 2} {1 if i % 2 else 2}:1\n" for i in range(64)))
    pred = tmp_path / "pred.txt"
    pred.write_text("1 1:1\n\n0 2:1\n")
    cfg = tmp_path / "c.cfg"
    cfg.write_text(textwrap.dedent(f"""
        [General]
        vocabulary_size = 10
        factor_num = 2
        model_file = {tmp_path}/m/fm
        [Train]
        train_files = {train}
        epoch_num = 2
        batch_size = 16
        learning_rate = 0.1
        [Predict]
        predict_files = {pred}
        score_path = {tmp_path}/score
    """))
    assert run_tffm.main(["train", str(cfg)]) == 0
    assert run_tffm.main(["predict", str(cfg)]) == 0
    scores = (tmp_path / "score" / "pred.txt.score").read_text().splitlines()
    assert len(scores) == 3                   # one per input line, blank too
    assert float(scores[1]) == pytest.approx(0.5)  # empty example -> sigmoid(0)


def test_kernel_validated():
    with pytest.raises(ValueError):
        FmConfig(kernel="cuda")


def test_multiprocess_rejects_unlimited_features(tmp_path, monkeypatch):
    # max_features_per_example = 0 ("unlimited") must be refused up front
    # in multi-process mode: an over-long example caught lazily mid-run
    # would kill one worker between collectives and hang its peers.
    import jax
    from fast_tffm_tpu.train import train
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    data = tmp_path / "t.txt"
    data.write_text("1 1:1\n0 2:1\n")
    cfg = FmConfig(vocabulary_size=8, batch_size=2,
                   train_files=(str(data),),
                   model_file=str(tmp_path / "m" / "fm"),
                   max_features_per_example=0)
    with pytest.raises(ValueError, match="max_features_per_example"):
        train(cfg)


def test_fast_path_extends_ladder_like_generic(tmp_path):
    # max_features_per_example past the ladder top: the fast path must
    # emit the same extended power-of-two bucket the generic path
    # compiles for (512 here), not a non-ladder width.
    from fast_tffm_tpu.data.cparser import available
    from fast_tffm_tpu.data.pipeline import batch_iterator
    if not available():
        pytest.skip("C++ parser unavailable")
    data = tmp_path / "t.txt"
    long_line = "1 " + " ".join(f"{i}:1" for i in range(300))
    data.write_text(long_line + "\n0 1:1\n")
    cfg = FmConfig(vocabulary_size=5000, batch_size=2,
                   bucket_ladder=(4, 8), max_features_per_example=300,
                   shuffle=False)
    batches = list(batch_iterator(cfg, [str(data)], training=True,
                                  epochs=1))
    assert batches[0].local_idx.shape[1] == 512
    assert batches[0].num_real == 2


def test_ignored_reference_knobs_warn(tmp_path):
    from fast_tffm_tpu.config import load_config
    p = tmp_path / "c.cfg"
    p.write_text("[General]\nvocabulary_block_num = 100\n"
                 "[Train]\nshuffle_threads = 4\n")
    with pytest.warns(UserWarning, match="vocabulary_block_num"):
        cfg = load_config(str(p))
    # shuffle_threads is no longer a warned no-op: it maps to the input
    # pipeline's prefetch lookahead (clamped to [2, 8]).
    assert cfg.prefetch_depth == 4
    import dataclasses
    assert dataclasses.replace(cfg, shuffle_threads=99).prefetch_depth == 8
    assert dataclasses.replace(cfg, shuffle_threads=0).prefetch_depth == 2


@pytest.mark.skipif(
    not orbax_enforces_template_shapes(),
    reason="installed orbax silently restores shape-mismatched "
           "templates (sharding-from-file path), so the actionable "
           "error can never trigger (ISSUE 3 triage)")
def test_checkpoint_shape_mismatch_is_actionable(tmp_path):
    # A checkpoint written under one config restored under another must
    # fail with a message naming the shapes and the fix, not orbax's
    # internal shape error.
    from fast_tffm_tpu.checkpoint import CheckpointState
    from fast_tffm_tpu.models.fm import init_accumulator, init_table
    from fast_tffm_tpu.checkpoint import checkpoint_template, ckpt_state
    model = str(tmp_path / "m" / "fm")
    cfg = FmConfig(vocabulary_size=64, factor_num=4, model_file=model)
    ckpt = CheckpointState(model)
    ckpt.save(1, *ckpt_state(cfg, init_table(cfg), init_accumulator(cfg)),
              vocabulary_size=cfg.vocabulary_size, force=True)
    ckpt.close()
    cfg2 = FmConfig(vocabulary_size=64, factor_num=8, model_file=model)
    ckpt2 = CheckpointState(model)
    with pytest.raises(ValueError, match="different config"):
        ckpt2.restore(template=checkpoint_template(cfg2))
    ckpt2.close()


def test_checkpoint_vocab_change_same_bucket_rejected(tmp_path):
    # vocabulary_size changes within the same 4096-row storage bucket
    # keep the stored shape identical, so the shape check can't fire;
    # the stored vocab leaf must catch it (a silent restore would turn
    # a trained row into the pad row).
    from fast_tffm_tpu.checkpoint import CheckpointState
    from fast_tffm_tpu.models.fm import init_accumulator, init_table
    from fast_tffm_tpu.checkpoint import (check_restored_vocab,
                                          checkpoint_template, ckpt_state)
    model = str(tmp_path / "m" / "fm")
    cfg = FmConfig(vocabulary_size=2000, factor_num=4, model_file=model)
    ckpt = CheckpointState(model)
    ckpt.save(1, *ckpt_state(cfg, init_table(cfg), init_accumulator(cfg)),
              vocabulary_size=cfg.vocabulary_size, force=True)
    ckpt.close()
    cfg2 = FmConfig(vocabulary_size=1000, factor_num=4, model_file=model)
    assert cfg2.ckpt_rows == cfg.ckpt_rows  # same storage bucket
    ckpt2 = CheckpointState(model)
    restored = ckpt2.restore(template=checkpoint_template(cfg2))
    ckpt2.close()
    with pytest.raises(ValueError, match="vocabulary_size=2000"):
        check_restored_vocab(cfg2, restored)


def test_profiler_closed_when_loop_raises(tmp_path):
    # A parse error mid-loop with the profiler window open must still
    # stop the trace (finally), or the next start_trace in this process
    # fails with "trace already in progress".
    import jax
    from fast_tffm_tpu.data.parser import ParseError
    from fast_tffm_tpu.train import train
    data = tmp_path / "t.txt"
    good = "".join(f"{i % 2} {i % 5}:1\n" for i in range(8))
    data.write_text(good + "1 not_an_id:1\n")
    cfg = FmConfig(vocabulary_size=8, batch_size=8, epoch_num=1,
                   shuffle=False, train_files=(str(data),),
                   model_file=str(tmp_path / "m" / "fm"),
                   profile_dir=str(tmp_path / "prof"),
                   profile_start_step=0, profile_num_steps=10)
    with pytest.raises(ParseError):
        train(cfg)
    jax.profiler.start_trace(str(tmp_path / "prof2"))  # must not raise
    jax.profiler.stop_trace()


def test_cluster_wiring_surface():
    from fast_tffm_tpu.parallel.distributed import (coordinator_address,
                                                    init_from_cluster)
    # Single-host cluster: no jax.distributed, trivial shard.
    assert init_from_cluster(FmConfig(), "worker", 0) == (0, 1)
    cfg = FmConfig(worker_hosts=("a:2230", "b:2230"))
    # Coordinator is chief worker's host on a shifted port (the worker
    # port itself belongs to the reference's gRPC surface).
    assert coordinator_address(cfg) == "a:3230"
    assert coordinator_address(FmConfig(worker_hosts=("a",))) == "a:8476"
    with pytest.raises(ValueError, match="out of range"):
        init_from_cluster(cfg, "worker", 5)
    with pytest.raises(ValueError, match="job_name"):
        init_from_cluster(cfg, "ps", 0)
