"""dedup=device (raw-ids) mode: the pipeline ships raw feature ids and
the jitted step runs jnp.unique on device — must be bit-equivalent to
the host-dedup path; and dedup=auto on one device, wired end-to-end
through the CLI (the host unique for a train step and for a sweep's
scorer alike; raw ids where the caller asks, as serve does)."""

import dataclasses
import os
import subprocess
import sys

import numpy as np
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.data.pipeline import batch_iterator
from fast_tffm_tpu.models.fm import (ModelSpec, batch_args, init_accumulator,
                                     init_table, make_score_fn,
                                     make_train_step)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write(tmp_path, n=96, seed=5, ffm=False, field_num=4):
    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(n):
        nnz = rng.integers(1, 12)
        ids = rng.choice(300, size=nnz, replace=False)
        if ffm:
            toks = [f"{int(rng.integers(0, field_num))}:{i}:"
                    f"{rng.random():.4f}" for i in ids]
        else:
            toks = [f"{i}:{rng.random():.4f}" for i in ids]
        lines.append(" ".join(["1" if rng.random() < 0.4 else "0"] + toks))
    p = tmp_path / "d.txt"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def _cfg(path, **kw):
    base = dict(vocabulary_size=300, factor_num=4, batch_size=16,
                train_files=(path,), shuffle=False,
                bucket_ladder=(4, 8, 16), max_features_per_example=16,
                learning_rate=0.1, factor_lambda=1e-4, bias_lambda=1e-4)
    base.update(kw)
    return FmConfig(**base)


def _train_all(cfg, spec, raw):
    table, acc = init_table(cfg, 0), init_accumulator(cfg)
    step = make_train_step(spec)
    losses = []
    for b in batch_iterator(cfg, cfg.train_files, training=True,
                            raw_ids=raw):
        table, acc, loss, scores = step(table, acc, **batch_args(b))
        losses.append(float(loss))
    return np.asarray(table), np.asarray(acc), losses


def test_device_dedup_matches_host(tmp_path):
    """Same data, host- vs device-side unique: identical losses, table,
    and accumulator (the unique pass location must be invisible)."""
    path = _write(tmp_path)
    cfg = _cfg(path)
    host = _train_all(cfg, dataclasses.replace(
        ModelSpec.from_config(cfg), dedup="host"), raw=False)
    dev_spec = dataclasses.replace(ModelSpec.from_config(cfg),
                                   dedup="device")
    dev = _train_all(cfg, dev_spec, raw=True)
    np.testing.assert_allclose(dev[2], host[2], rtol=1e-6)
    np.testing.assert_allclose(dev[0], host[0], rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(dev[1], host[1], rtol=1e-6, atol=1e-7)


def test_device_dedup_ffm_matches_host(tmp_path):
    """FFM raw-ids mode: fields ride along unchanged."""
    path = _write(tmp_path, ffm=True)
    cfg = _cfg(path, model_type="ffm", field_num=4)
    host = _train_all(cfg, dataclasses.replace(
        ModelSpec.from_config(cfg), dedup="host"), raw=False)
    dev_spec = dataclasses.replace(ModelSpec.from_config(cfg),
                                   dedup="device")
    dev = _train_all(cfg, dev_spec, raw=True)
    np.testing.assert_allclose(dev[2], host[2], rtol=1e-6)
    np.testing.assert_allclose(dev[0], host[0], rtol=1e-6, atol=1e-7)


def test_device_dedup_score_parity(tmp_path):
    path = _write(tmp_path, seed=9)
    cfg = _cfg(path)
    table = init_table(cfg, 3)
    spec_h = dataclasses.replace(ModelSpec.from_config(cfg), dedup="host")
    spec_d = dataclasses.replace(spec_h, dedup="device")
    sh, sd = [], []
    for raw, spec, out in ((False, spec_h, sh), (True, spec_d, sd)):
        fn = make_score_fn(spec)
        for b in batch_iterator(cfg, cfg.train_files, training=False,
                                raw_ids=raw):
            args = batch_args(b)
            args.pop("labels"), args.pop("weights")
            out.append(np.asarray(fn(table, **args))[:b.num_real])
    np.testing.assert_allclose(np.concatenate(sd), np.concatenate(sh),
                               rtol=1e-5, atol=1e-7)


def test_raw_batches_reconstruct_host_stream(tmp_path):
    """The raw-ids pipeline (C++ builder with dedup skipped) must carry
    exactly the ids the host-dedup pipeline encodes via uniq[li]."""
    path = _write(tmp_path, seed=11)
    cfg = _cfg(path)
    host = list(batch_iterator(cfg, cfg.train_files, training=True))
    raw = list(batch_iterator(cfg, cfg.train_files, training=True,
                              raw_ids=True))
    assert len(host) == len(raw)
    for h, r in zip(host, raw):
        assert r.uniq_ids is None
        want = np.asarray(h.uniq_ids)[h.local_idx]  # decode slot -> id
        np.testing.assert_array_equal(r.local_idx, want)
        np.testing.assert_array_equal(r.vals, h.vals)
        np.testing.assert_array_equal(r.labels, h.labels)


def test_mode_mismatch_raises(tmp_path):
    """A host-deduped batch into a device-dedup step must fail loudly at
    trace time — slot indices silently read as feature ids is the
    corruption this guard exists for."""
    import pytest
    path = _write(tmp_path, seed=13)
    cfg = _cfg(path)
    spec_d = dataclasses.replace(ModelSpec.from_config(cfg),
                                 dedup="device")
    step = make_train_step(spec_d)
    b = next(batch_iterator(cfg, cfg.train_files, training=True))
    with pytest.raises(ValueError, match="raw_ids"):
        step(init_table(cfg, 0), init_accumulator(cfg), **batch_args(b))
    with pytest.raises(ValueError, match="fixed-U"):
        next(batch_iterator(cfg, cfg.train_files, training=True,
                            raw_ids=True, fixed_shape=True))


def test_cli_e2e_auto_resolves_by_use(tmp_path):
    """On a single device, dedup=auto takes the host unique whatever
    the use: the train step, evaluate()'s sweep and predict's all get
    ``uniq_ids`` with U a ladder rung under B*L + 1, and both regime
    lines say ``dedup=host``; serve's ``CompiledScorer(cfg,
    dedup="device")`` still ships raw batches and needs no U axis. The
    full CLI train->predict must work and produce sane scores (run in
    a subprocess with exactly one CPU device — the in-process test env
    pins 8 virtual devices, which resolves auto to host anyway)."""
    path = _write(tmp_path, n=64, seed=17)
    cfg_path = tmp_path / "dd.cfg"
    cfg_path.write_text(f"""
[General]
vocabulary_size = 300
factor_num = 4
model_file = {tmp_path}/model/fm

[Train]
train_files = {path}
validation_files = {path}
epoch_num = 2
batch_size = 16
learning_rate = 0.1
shuffle = False

[Predict]
predict_files = {path}
score_path = {tmp_path}/score
""")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    code = (
        "import jax, numpy as np, run_tffm\n"
        "from fast_tffm_tpu import scoring, train as tr\n"
        "from fast_tffm_tpu.config import load_config\n"
        "from fast_tffm_tpu.data import pipeline\n"
        "from fast_tffm_tpu.models import fm\n"
        "assert jax.device_count() == 1, jax.device_count()\n"
        f"cfg = load_config(r'{cfg_path}')\n"
        "s = fm.ModelSpec.from_config(cfg)\n"
        "assert s.dedup == 'host' and not fm.ships_raw_batches(s)\n"
        "assert 'dedup=host ' in fm.regime_line(s, cfg)\n"
        "def rung(u, li):\n"
        "    B, L = li.shape\n"
        "    return u in pipeline._uniq_ladder(B, L) and u < B * L + 1\n"
        "steps, sweeps, preds = [], [], []\n"
        "make = tr.make_train_step\n"
        "def probed(spec):\n"
        "    step = make(spec)\n"
        "    def call(table, acc, **kw):\n"
        "        steps.append(rung(kw['uniq_ids'].shape[0],\n"
        "                          kw['local_idx']))\n"
        "        return step(table, acc, **kw)\n"
        "    return call\n"
        "tr.make_train_step = probed\n"
        "scorer = tr.make_batch_scorer\n"
        "def swept(spec, **kw):\n"
        "    fn = scorer(spec, **kw)\n"
        "    def call(table, args):\n"
        "        sweeps.append(rung(args['uniq_ids'].shape[0],\n"
        "                           args['local_idx']))\n"
        "        return fn(table, args)\n"
        "    return call\n"
        "tr.make_batch_scorer = swept\n"
        "score_batch = scoring.CompiledScorer.score_batch\n"
        "def scored(self, table, batch):\n"
        "    preds.append(rung(batch.uniq_ids.shape[0], batch.local_idx))\n"
        "    return score_batch(self, table, batch)\n"
        "scoring.CompiledScorer.score_batch = scored\n"
        f"assert run_tffm.main(['train', r'{cfg_path}']) == 0\n"
        f"assert run_tffm.main(['predict', r'{cfg_path}']) == 0\n"
        "assert len(steps) == 8 and all(steps), steps\n"
        "assert len(sweeps) == 8 and all(sweeps), sweeps\n"
        "assert len(preds) == 4 and all(preds), preds\n"
        # serve's handle: raw batches, [B, L] the only axes
        "scoring.CompiledScorer.score_batch = score_batch\n"
        "srv = scoring.CompiledScorer(cfg, dedup='device')\n"
        "assert srv.raw and srv.spec.dedup == 'device'\n"
        "assert 'dedup=device ' in fm.regime_line(srv.spec, cfg)\n"
        "b = next(pipeline.batch_iterator(cfg, cfg.predict_files,\n"
        "         training=False, epochs=1, raw_ids=srv.raw))\n"
        "assert b.uniq_ids is None\n"
        "table = fm.init_table(cfg, 0)\n"
        "got = np.asarray(srv.score_batch(table, b))\n"
        "h = next(pipeline.batch_iterator(cfg, cfg.predict_files,\n"
        "         training=False, epochs=1))\n"
        "want = np.asarray(scoring.CompiledScorer(cfg)\n"
        "                  .score_batch(table, h))\n"
        "np.testing.assert_array_equal(got, want)\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "train regime: backend=cpu devices=1 dedup=host" in out.stderr
    assert "predict regime: backend=cpu devices=1 dedup=host" in out.stderr
    scores = np.loadtxt(tmp_path / "score" / "d.txt.score")
    assert len(scores) == 64
    assert np.isfinite(scores).all() and (0 <= scores).all() \
        and (scores <= 1).all()


def test_checkpoint_crosses_dedup_modes(tmp_path):
    """A checkpoint is mode-free state: training saved under dedup=host
    must resume under dedup=device with the identical continued
    trajectory — the unique-pass location cannot leak into persistence.
    Runs in a 1-CPU-device subprocess (dedup=device is single-device;
    the in-process env pins 8)."""
    path = _write(tmp_path, n=64, seed=21)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    code = f"""
import shutil
import numpy as np
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.train import train

def cfg_for(dedup, epochs, model):
    return FmConfig(vocabulary_size=300, factor_num=4, batch_size=16,
                    train_files=(r'{path}',), shuffle=False,
                    bucket_ladder=(4, 8, 16),
                    max_features_per_example=16, learning_rate=0.1,
                    epoch_num=epochs, dedup=dedup,
                    model_file=r'{tmp_path}' + '/' + model + '/fm')

import logging
records = []
class Grab(logging.Handler):
    def emit(self, r):
        records.append(r.getMessage())
_lg = logging.getLogger('fast_tffm_tpu')
_lg.addHandler(Grab())
_lg.setLevel(logging.INFO)  # get_logger skips setup once handlers exist

train(cfg_for('host', 1, 'a'))
shutil.copytree(r'{tmp_path}/a', r'{tmp_path}/b')
t_host = np.asarray(train(cfg_for('host', 3, 'a')))
t_dev = np.asarray(train(cfg_for('device', 3, 'b')))
# Guard against vacuous success: both resumed runs must actually have
# RESTORED (a fresh-start pair would also match, trivially).
restores = [m for m in records if m.startswith('restored checkpoint')]
assert len(restores) == 2, records
np.testing.assert_allclose(t_dev, t_host, rtol=1e-6, atol=1e-7)
print('cross-mode resume ok')
"""
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "cross-mode resume ok" in out.stdout
