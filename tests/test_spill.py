"""Spill observability (round-2 review, item 5): undersized uniq_bucket must
be visible (SpillStats), never lossy, on both the C++ fast path and the
generic path; probe_uniq_bucket must not be fooled by a sparse head."""

import os

import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.pipeline import (SPILL_WARN_FRACTION, SpillStats,
                                         batch_iterator, effective_L_cap,
                                         probe_uniq_bucket)


def _dense_file(path, n_lines, ids_per_line, id_stride=1, start=0):
    """Each line holds ``ids_per_line`` distinct ids, lines disjoint when
    id_stride >= ids_per_line — so unique count grows fast."""
    with open(path, "w") as fh:
        for i in range(n_lines):
            base = start + i * id_stride
            toks = " ".join(f"{base + j}:1" for j in range(ids_per_line))
            fh.write(f"{i % 2} {toks}\n")


def _run(cfg, path, **kw):
    stats = SpillStats()
    batches = list(batch_iterator(cfg, [str(path)], training=True,
                                  epochs=1, fixed_shape=True,
                                  uniq_bucket=cfg.uniq_bucket,
                                  stats=stats, **kw))
    return batches, stats


@pytest.mark.parametrize("generic", [False, True])
def test_spill_counted_and_lossless(tmp_path, generic):
    # 64 lines x 8 disjoint ids: a 16-line batch needs 128 uniques + pad,
    # but the bucket holds 64 -> every batch must close early (spill).
    path = tmp_path / "dense.txt"
    _dense_file(path, 64, 8, id_stride=8)
    cfg = FmConfig(vocabulary_size=4096, batch_size=16, uniq_bucket=64,
                   max_features_per_example=16, bucket_ladder=(16,),
                   shuffle=False)
    # weight_files force the generic (Python make_device_batch) path —
    # keep_empty no longer does (it is a C++ builder mode since ABI 4).
    kw = {}
    if generic:
        wpath = tmp_path / "w.txt"
        wpath.write_text("1.0\n" * 64)
        kw["weight_files"] = (str(wpath),)
    batches, stats = _run(cfg, path, **kw)
    assert stats.spilled_batches > 0
    assert stats.batches == len(batches)
    assert stats.fill_fraction < 1.0
    assert stats.spill_fraction > 0.5
    # Lossless: every line emitted exactly once, in order.
    assert stats.real_examples == 64
    assert sum(b.num_real for b in batches) == 64
    for b in batches:
        assert len(b.uniq_ids) == 64          # shape stays fixed
        assert b.num_real < cfg.batch_size    # every batch spilled here


def test_no_spill_counts_clean(tmp_path):
    path = tmp_path / "sparse.txt"
    _dense_file(path, 64, 4, id_stride=0)     # all lines share 4 ids
    cfg = FmConfig(vocabulary_size=4096, batch_size=16, uniq_bucket=64,
                   max_features_per_example=16, bucket_ladder=(16,),
                   shuffle=False)
    batches, stats = _run(cfg, path)
    assert stats.spilled_batches == 0
    assert stats.fill_fraction == 1.0
    assert stats.real_examples == 64


def test_probe_sees_dense_tail(tmp_path):
    """Sparse-first data: a head-only probe would pick the minimum
    bucket and every tail batch would spill; the 3-point probe must see
    the dense tail."""
    path = tmp_path / "sorted.txt"
    with open(path, "w") as fh:
        for i in range(512):                  # sparse head: 4 shared ids
            fh.write("1 0:1 1:1 2:1 3:1\n")
        for i in range(512):                  # dense tail: disjoint ids
            base = 100 + i * 12
            toks = " ".join(f"{base + j}:1" for j in range(12))
            fh.write(f"0 {toks}\n")
    cfg = FmConfig(vocabulary_size=1 << 16, batch_size=128,
                   max_features_per_example=16, bucket_ladder=(16,),
                   shuffle=False)
    b = probe_uniq_bucket(cfg, [str(path)])
    # Dense tail batch: 128 lines x 12 disjoint ids ~ 1536 uniques ->
    # probe must return >= 4096 (2x headroom, pow2); head alone gives 64.
    assert b >= 2048, b


def test_effective_L_cap_shared():
    cfg = FmConfig(bucket_ladder=(8, 16), max_features_per_example=100)
    assert effective_L_cap(cfg) == 128        # pow2 extension past ladder
    cfg2 = FmConfig(bucket_ladder=(8, 64), max_features_per_example=32)
    assert effective_L_cap(cfg2) == 64


def test_probe_sees_dense_later_file(tmp_path):
    """Day-partitioned multi-file data whose LATER files are denser: the
    probe samples first + last + largest files, so a dense final file
    sets the bucket even when file 0 is all-sparse (round-3 review, weak #3)."""
    sparse = tmp_path / "day0.txt"
    _dense_file(sparse, 512, 4, id_stride=0)   # 4 shared ids throughout
    dense = tmp_path / "day1.txt"
    with open(dense, "w") as fh:
        for i in range(512):
            base = 100 + i * 12
            toks = " ".join(f"{base + j}:1" for j in range(12))
            fh.write(f"0 {toks}\n")
    cfg = FmConfig(vocabulary_size=1 << 16, batch_size=128,
                   max_features_per_example=16, bucket_ladder=(16,),
                   shuffle=False)
    assert probe_uniq_bucket(cfg, [str(sparse)]) == 64     # sparse alone
    assert probe_uniq_bucket(cfg, [str(sparse), str(dense)]) >= 2048


def test_adapt_uniq_bucket_raises_on_spill():
    """Epoch-boundary adaptation: job-wide spill above the warn
    threshold doubles the bucket (capped at the worst-case top); an
    explicit config or a clean epoch leaves it alone."""
    import logging
    from fast_tffm_tpu.data.pipeline import uniq_bucket_top
    from fast_tffm_tpu.train import adapt_uniq_bucket
    logger = logging.getLogger("test")
    cfg = FmConfig(vocabulary_size=1 << 16, batch_size=128,
                   max_features_per_example=16, bucket_ladder=(16,))
    top = uniq_bucket_top(cfg)
    assert adapt_uniq_bucket(cfg, 256, spilled=50, batches=100,
                             logger=logger) == 512
    assert adapt_uniq_bucket(cfg, 256, spilled=5, batches=100,
                             logger=logger) == 256          # clean epoch
    assert adapt_uniq_bucket(cfg, top, spilled=50, batches=100,
                             logger=logger) == top          # capped
    assert adapt_uniq_bucket(cfg, top // 2, spilled=50, batches=50,
                             logger=logger) == top
    pinned = FmConfig(vocabulary_size=1 << 16, batch_size=128,
                      max_features_per_example=16, bucket_ladder=(16,),
                      uniq_bucket=256)
    assert adapt_uniq_bucket(pinned, 256, spilled=50, batches=100,
                             logger=logger) == 256          # explicit cfg
    assert adapt_uniq_bucket(cfg, 256, spilled=0, batches=0,
                             logger=logger) == 256          # no batches


def test_adapt_uniq_bucket_shrinks_on_low_fill():
    """Shrink branch (round-4 review: the adaptive bucket only grew, so
    an overshot probe or an early dense file inflated the gather/
    scatter width for the rest of the job): a spill-free epoch whose
    densest batch filled < SHRINK_FILL_FRACTION of the bucket halves
    it — never below 64 or the per-example cap, never when any batch
    spilled, never against an explicit config."""
    import logging
    from fast_tffm_tpu.train import SHRINK_FILL_FRACTION, adapt_uniq_bucket
    logger = logging.getLogger("test")
    cfg = FmConfig(vocabulary_size=1 << 16, batch_size=128,
                   max_features_per_example=16, bucket_ladder=(16,))
    kw = dict(spilled=0, batches=100, logger=logger)
    assert adapt_uniq_bucket(cfg, 512, max_uniq=100, **kw) == 256
    # fill at/above the threshold keeps the width
    at = int(512 * SHRINK_FILL_FRACTION)
    assert adapt_uniq_bucket(cfg, 512, max_uniq=at + 1, **kw) == 512
    # floor: never below 64
    assert adapt_uniq_bucket(cfg, 64, max_uniq=4, **kw) == 64
    assert adapt_uniq_bucket(cfg, 128, max_uniq=4, **kw) == 64
    # floor: the halved bucket must still exceed the per-example cap
    # (128 -> 64 would leave a full 100-feature example unable to fit)
    wide = FmConfig(vocabulary_size=1 << 16, batch_size=128,
                    max_features_per_example=100, bucket_ladder=(128,))
    assert adapt_uniq_bucket(wide, 128, max_uniq=20, **kw) == 128
    # any spill this epoch blocks the shrink (densities are recurring)
    assert adapt_uniq_bucket(cfg, 512, spilled=1, batches=100,
                             max_uniq=100, logger=logger) == 512
    # unknown density (max_uniq=0, e.g. no stats) never shrinks
    assert adapt_uniq_bucket(cfg, 512, max_uniq=0, **kw) == 512
    # explicit config is never overridden
    pinned = FmConfig(vocabulary_size=1 << 16, batch_size=128,
                      max_features_per_example=16, bucket_ladder=(16,),
                      uniq_bucket=512)
    assert adapt_uniq_bucket(pinned, 512, max_uniq=100, **kw) == 512


def test_adaptive_bucket_clears_spill_by_epoch2(tmp_path):
    """Heterogeneous-density multi-file input where the dense file is
    the MIDDLE one (first+last+largest probe misses it when sizes
    match): epoch 1 spills, the epoch-boundary adaptation doubles the
    bucket, epoch 2 runs spill-free (round-3 review, next-round #6)."""
    import logging
    from fast_tffm_tpu.train import adapt_uniq_bucket
    files = []
    for name, dense in (("a.txt", False), ("b.txt", True),
                        ("c.txt", False)):
        p = tmp_path / name
        with open(p, "w") as fh:
            for i in range(256):
                if dense:
                    base = 1000 + i * 12
                    toks = " ".join(f"{base + j}:1" for j in range(12))
                else:
                    toks = "0:1 1:1 2:1 3:1"
                fh.write(f"1 {toks}\n")
        files.append(str(p))
    # Pad the sparse files to the dense file's byte size so "largest"
    # cannot accidentally pick the dense middle file.
    target = max(os.path.getsize(f) for f in files)
    for f in (files[0], files[2]):
        with open(f, "a") as fh:
            while os.path.getsize(f) < target:
                fh.write("1 0:1 1:1 2:1 3:1\n")
    cfg = FmConfig(vocabulary_size=1 << 16, batch_size=128,
                   max_features_per_example=16, bucket_ladder=(16,),
                   shuffle=False)
    bucket = probe_uniq_bucket(cfg, files)
    assert bucket <= 128  # the probe misses the dense middle file

    def run_epoch(b):
        stats = SpillStats()
        for _ in batch_iterator(cfg, files, training=True, epochs=1,
                                fixed_shape=True, uniq_bucket=b,
                                stats=stats):
            pass
        return stats

    s1 = run_epoch(bucket)
    assert s1.spill_fraction > SPILL_WARN_FRACTION
    logger = logging.getLogger("test")
    for _ in range(8):  # train() adapts once per epoch boundary
        new = adapt_uniq_bucket(cfg, bucket, s1.spilled_batches,
                                s1.batches, logger)
        if new == bucket:
            break
        bucket = new
        s1 = run_epoch(bucket)
    # The adaptation's contract: drive spill below the warn threshold
    # (it stops doubling there by design — a stray spilled batch is
    # normal; 67% -> ~7% on this data, fill 36% -> 94%).
    assert s1.spill_fraction <= SPILL_WARN_FRACTION, s1.describe()
    assert s1.fill_fraction > 0.9, s1.describe()
