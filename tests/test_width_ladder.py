"""ISSUE 34: the width a batch ships at climbs quarter-octave rungs.

(a) the default ladder and ``_ladder_fit`` on it, (b) both builders at
the narrower width against the same batch at the old doubling rung,
(c) one train step on each, one device and the CPU mesh, (d) the
scoring server's widths, which stay a doubling subset.
"""

import jax
import numpy as np
import pytest

from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data import pipeline
from fast_tffm_tpu.data.pipeline import (RowShards, _ladder_fit,
                                         batch_iterator)
from fast_tffm_tpu.models.fm import (ModelSpec, batch_args, init_accumulator,
                                     init_table, make_train_step)
from fast_tffm_tpu.parallel.sharded import (init_sharded_state, make_mesh,
                                            make_sharded_train_step,
                                            shard_batch)
from fast_tffm_tpu.serve.server import width_rung_ladder

DEFAULT = FmConfig().bucket_ladder
DOUBLING = (8, 16, 32, 64, 128, 256)
# model -> (features an example, the rung they ride, the doubling rung)
WIDTHS = {"fm": (39, 40, 64), "ffm": (22, 24, 32)}


# ---- (a) the ladder ----------------------------------------------------

@pytest.mark.parametrize("rule", [
    "multiples of 8", "strictly rising", "from 8 to 256",
    "no rung from 32 up is over 1.25 times the one below",
    "holds every doubling rung"])
def test_default_ladder(rule):
    lad = DEFAULT
    steps = list(zip(lad, lad[1:]))
    assert {
        "multiples of 8": all(b % 8 == 0 for b in lad),
        "strictly rising": all(a < b for a, b in steps),
        "from 8 to 256": (lad[0], lad[-1]) == (8, 256),
        "no rung from 32 up is over 1.25 times the one below": all(
            b <= 1.25 * a for a, b in steps if a >= 32),
        "holds every doubling rung": set(DOUBLING) <= set(lad),
    }[rule]


@pytest.mark.parametrize("n, rung", [
    (1, 8), (8, 8), (9, 16), (22, 24), (24, 24), (25, 32), (33, 40),
    (39, 40), (41, 48), (63, 64), (64, 64), (65, 80), (129, 160),
    (256, 256), (257, 512), (600, 1024)])
def test_ladder_fit_on_the_default_ladder(n, rung):
    assert _ladder_fit(n, DEFAULT) == rung


def test_a_ladder_the_user_sets_is_kept():
    cfg = FmConfig(bucket_ladder=(8, 64), max_features_per_example=64)
    assert cfg.bucket_ladder == (8, 64)
    assert _ladder_fit(39, cfg.bucket_ladder) == 64
    assert pipeline.effective_L_cap(FmConfig()) == 256


# ---- (b) the builders --------------------------------------------------

def _cfg(path, model, ladder=DEFAULT, **kw):
    base = dict(vocabulary_size=6000, factor_num=4, batch_size=32,
                train_files=(str(path),), epoch_num=1, shuffle=False,
                learning_rate=0.1, factor_lambda=1e-4, bias_lambda=1e-4,
                max_features_per_example=64, bucket_ladder=ladder)
    if model == "ffm":
        base.update(model_type="ffm", field_num=WIDTHS["ffm"][0])
    base.update(kw)
    return FmConfig(**base)


def _write(path, model, rng, n_batches=2):
    """Click-log rows: every example has the corpus's full width (39
    features, or one per field of 22), ids skewed so rows repeat."""
    n = WIDTHS[model][0]
    skew = 1.0 / (1.0 + np.arange(6000)) ** 0.7
    skew /= skew.sum()
    lines = []
    for _ in range(n_batches * 32):
        ids = rng.choice(6000, size=n, replace=False, p=skew)
        feats = " ".join(
            (f"{f}:" if model == "ffm" else "")
            + f"{i}:{rng.random() + 0.1:.3f}" for f, i in enumerate(ids))
        lines.append(f"{int(rng.random() < 0.4)} {feats}")
    path.write_text("\n".join(lines) + "\n")


def _batches(cfg, builder, monkeypatch, **kw):
    def no_builder(*a, **k):
        raise RuntimeError("forced generic path")
    with monkeypatch.context() as m:
        if builder == "python":
            m.setattr(pipeline, "_make_builder", no_builder)
        return list(batch_iterator(cfg, cfg.train_files, training=True,
                                   **kw))


@pytest.mark.parametrize("raw_ids", [False, True], ids=["slots", "raw"])
@pytest.mark.parametrize("model", list(WIDTHS))
@pytest.mark.parametrize("builder", ["cpp", "python"])
def test_a_narrow_batch_is_the_wide_batch_less_its_pad_columns(
        tmp_path, monkeypatch, builder, model, raw_ids):
    """At the new rung a builder gives the first columns of what it
    gives at the doubling rung, byte for byte, with the same real
    cells, unique rows and U; what the wide batch holds beyond is pad."""
    _, rung, doubling = WIDTHS[model]
    path = tmp_path / "train.txt"
    _write(path, model, np.random.default_rng(34))
    narrow = _batches(_cfg(path, model), builder, monkeypatch,
                      raw_ids=raw_ids)
    wide = _batches(_cfg(path, model, DOUBLING), builder, monkeypatch,
                    raw_ids=raw_ids)
    assert len(narrow) == len(wide) == 2
    for a, b in zip(narrow, wide):
        assert a.vals.shape == (32, rung) and b.vals.shape == (32, doubling)
        for name in ("local_idx", "vals", "fields"):
            x, y = getattr(a, name), getattr(b, name)
            if x is None:
                assert y is None and model == "fm"
                continue
            assert x.dtype == y.dtype
            assert x.tobytes() == np.ascontiguousarray(
                y[:, :rung]).tobytes(), name
        for name in ("labels", "weights", "uniq_ids"):
            x, y = getattr(a, name), getattr(b, name)
            assert (x is None and y is None and raw_ids) or (
                x.tobytes() == y.tobytes()), name
        assert a.nnz == b.nnz and a.num_real == b.num_real
        if a.nnz is not None:
            assert a.nnz == np.count_nonzero(a.vals)
        assert not b.vals[:, rung:].any()
        pad_cell = b.local_idx[:, rung:]
        assert (pad_cell == pad_cell.flat[0]).all()


# ---- (c) the step ------------------------------------------------------

def _mesh(shape):
    n_data, n_model = shape
    return make_mesh(jax.devices()[:n_data * n_model], model_axis=n_model)


@pytest.mark.parametrize("model", list(WIDTHS))
@pytest.mark.parametrize("shape", [None, (4, 1), (2, 2)],
                         ids=["one device", "mesh 4x1", "mesh 2x2"])
def test_a_step_on_the_narrow_batch_is_the_step_on_the_wide_one(
        tmp_path, monkeypatch, shape, model):
    """A pad cell weighs nothing: loss, scores and every touched row
    after one step agree to float tolerance, and no other row moves."""
    _, rung, doubling = WIDTHS[model]
    path = tmp_path / "train.txt"
    _write(path, model, np.random.default_rng(35), n_batches=1)
    cfg = _cfg(path, model)
    spec = ModelSpec.from_config(cfg)
    if shape is None:
        shards, place = None, (lambda **a: a)
        step = make_train_step(spec)
        state = lambda: (init_table(cfg, 3), init_accumulator(cfg))
    else:
        mesh = _mesh(shape)
        shards = RowShards.of(cfg, 4)
        place = lambda **a: shard_batch(mesh, **a)
        step = make_sharded_train_step(spec, mesh)
        state = lambda: init_sharded_state(cfg, mesh, seed=3)
    before = [np.asarray(x) for x in state()]
    outs = []
    for ladder, L in ((DEFAULT, rung), (DOUBLING, doubling)):
        batch, = _batches(_cfg(path, model, ladder), "cpp", monkeypatch,
                          row_shards=shards)
        assert batch.vals.shape == (32, L)
        table, acc, loss, scores = step(*state(),
                                        **place(**batch_args(batch)))
        outs.append((batch, np.asarray(table), np.asarray(acc),
                     float(loss), np.asarray(scores)))
    (a, table_a, acc_a, loss_a, scores_a), (b, table_b, acc_b, loss_b,
                                            scores_b) = outs
    np.testing.assert_array_equal(a.uniq_ids, b.uniq_ids)
    np.testing.assert_allclose(loss_a, loss_b, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(scores_a, scores_b, rtol=1e-5, atol=1e-6)
    rows = np.unique(a.uniq_ids[a.uniq_ids != cfg.pad_id])
    assert 100 < len(rows) < cfg.vocabulary_size
    for now_a, now_b, was in ((table_a, table_b, before[0]),
                              (acc_a, acc_b, before[1])):
        assert not np.array_equal(now_a[rows], was[rows])
        np.testing.assert_allclose(now_a[rows], now_b[rows],
                                   rtol=1e-5, atol=1e-7)
        rest = np.setdiff1d(np.arange(len(was)), rows)
        assert now_a[rest].tobytes() == was[rest].tobytes()
        assert now_b[rest].tobytes() == was[rest].tobytes()


# ---- (d) the server's widths -------------------------------------------

@pytest.mark.parametrize("ladder, max_features, kept", [
    (DEFAULT, 256, DOUBLING),
    (DOUBLING, 256, DOUBLING),
    (DEFAULT, 64, (8, 16, 32, 64)),
    (DEFAULT, 39, (8, 16, 40)),
    (DEFAULT, 22, (8, 24)),
    ((8, 16), 16, (8, 16)),
    ((4, 8, 16), 16, (4, 8, 16)),
    ((8, 12, 16), 16, (8, 16)),
    ((32, 64), 64, (32, 64)),
    ((48,), 48, (48,)),
])
def test_the_server_keeps_a_doubling_subset_of_its_ladder(
        ladder, max_features, kept):
    got = width_rung_ladder(ladder, max_features)
    assert got == kept
    assert set(got) <= set(ladder) and got[-1] >= max_features
    assert all(2 * a <= b for a, b in zip(got, got[1:]))
    old = [b for b in DOUBLING if b <= _ladder_fit(max_features, DOUBLING)]
    if ladder is DEFAULT:
        assert len(got) <= len(old)



# ---- the benchmark's corpora -------------------------------------------

@pytest.mark.parametrize("config, rung, fill", [
    ("fm-k16-criteo1tb", 40, 0.975),
    ("ffm-k4-avazu", 24, 22 / 24),
    ("fm-k16-criteo1tb-x4", 40, 0.975),
])
def test_the_benchmarks_corpora_ride_the_rung_just_over_them(config, rung,
                                                             fill):
    """What ``cell_fill`` reads on the chip: every example of a train
    cell has its configuration's features, no configuration sets a
    ladder of its own, and the default ladder's rung over them leaves
    the pad cells PERF.md section 4 says."""
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "configs",
                           config + ".json")) as fh:
        cfg = json.load(fh)
    assert not any("bucket_ladder" in section
                   for section in cfg["program"].values())
    n = (cfg["features"]["numeric"]
         + len(cfg["features"]["categorical_cardinalities"]))
    assert _ladder_fit(n, DEFAULT) == rung
    assert n / rung == pytest.approx(fill)
    assert n / _ladder_fit(n, DOUBLING) < 0.7
