"""A one-device validation sweep takes the host unique and the fitted
slots (ISSUE 45): ``evaluate()`` under ``dedup = auto`` scores the very
numbers an explicit ``dedup = device`` scores from raw ids, and its
plane counts the slots it ships under ``validation_plane/``. One CPU
device in a subprocess: the suite's rig pins eight virtual devices."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SWEEP = r"""
import dataclasses, json, sys
import jax, numpy as np
from fast_tffm_tpu import train as tr
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.models import fm
from fast_tffm_tpu.obs.telemetry import RunTelemetry, activate

assert jax.device_count() == 1, jax.device_count()
path, out, model = sys.argv[1:4]
ffm = model == "ffm"
cfg = FmConfig(vocabulary_size=5000, factor_num=4, batch_size=64,
               train_files=(path,), validation_files=(path,),
               shuffle=False, bucket_ladder=(4, 8, 16),
               max_features_per_example=16,
               **(dict(model_type="ffm", field_num=4) if ffm else {}))
table = fm.init_table(cfg, 3)


class Scores:
    def __init__(self):
        self.chunks = []

    def update(self, s, y, w):
        self.chunks.append(np.array(s, copy=True))


result = {}
for dedup in ("auto", "device"):
    c = dataclasses.replace(cfg, dedup=dedup)
    wires = []
    scorer = tr.make_batch_scorer

    def probed(spec, **kw):
        fn = scorer(spec, **kw)

        def call(table, args):
            u = args.get("uniq_ids")
            wires.append(None if u is None else int(u.shape[0]))
            return fn(table, args)
        return call
    tr.make_batch_scorer = probed
    tel = RunTelemetry(f"{out}/{dedup}.jsonl", meta={"kind": "t"})
    seen = Scores()
    try:
        with activate(tel):
            auc, n = tr.evaluate(c, table, c.validation_files, collect=seen)
    finally:
        tel.close()
        tr.make_batch_scorer = scorer
    scores = np.concatenate(seen.chunks)
    np.save(f"{out}/{dedup}.npy", scores)
    result[dedup] = {"auc": auc, "n": n, "wires": wires,
                     "spec": fm.ModelSpec.from_config(c).dedup}
print(json.dumps(result))
"""


def _corpus(tmp_path, ffm, n=5 * 64 + 17, seed=45):
    """Click-log shaped: ids repeat across a batch (Zipf a=1.35)."""
    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(n):
        ids = np.unique(rng.zipf(1.35, size=16) % 5000)
        toks = [(f"{int(rng.integers(0, 4))}:" if ffm else "")
                + f"{i}:{rng.random():.4f}" for i in ids]
        lines.append(" ".join(["1" if rng.random() < 0.3 else "0"] + toks))
    p = tmp_path / "held_out.txt"
    p.write_text("\n".join(lines) + "\n")
    return str(p)


def _counters(path):
    last = {}
    with open(path) as fh:
        for line in fh:
            ev = json.loads(line)
            if ev.get("event") == "metrics":
                last = ev["counters"]
    return last


@pytest.mark.parametrize("model", ["fm", "ffm"])
def test_a_one_device_sweep_scores_the_same_bits_on_fitted_slots(
        tmp_path, model):
    from fast_tffm_tpu.data.pipeline import _uniq_ladder
    path = _corpus(tmp_path, ffm=model == "ffm")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", SWEEP, path, str(tmp_path),
                        model], cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode == 0, p.stdout + p.stderr
    said = json.loads(p.stdout.strip().splitlines()[-1])
    auto, device = said["auto"], said["device"]
    assert (auto["spec"], device["spec"]) == ("host", "device")
    assert auto["n"] == device["n"] == 5 * 64 + 17
    # the same AUC, and the scores equal to the bit
    assert auto["auc"] == device["auc"] and 0.0 < auto["auc"] < 1.0
    a = np.load(tmp_path / "auto.npy")
    d = np.load(tmp_path / "device.npy")
    assert a.dtype == d.dtype == np.float32 and len(a) == auto["n"]
    np.testing.assert_array_equal(a.view(np.uint32), d.view(np.uint32))
    # the wire: six calls each; U a rung under B*L + 1, or no U at all
    assert len(auto["wires"]) == len(device["wires"]) == 6
    assert all(u in _uniq_ladder(64, 16)[:-1] for u in auto["wires"])
    assert device["wires"] == [None] * 6
    # the counter that says the mechanism engaged
    fitted = _counters(tmp_path / "auto.jsonl")
    raw = _counters(tmp_path / "device.jsonl")
    assert fitted["validation_plane/uniq_slots"] == sum(auto["wires"])
    assert (0.5 * fitted["validation_plane/uniq_slots"]
            < fitted["validation_plane/uniq_rows"]
            < fitted["validation_plane/uniq_slots"])
    assert raw["validation_plane/batches"] == 6
    assert "validation_plane/uniq_rows" not in raw
    assert "validation_plane/uniq_slots" not in raw
    # and neither sweep counted its batches or slots as the training
    # plane's (``uniq_slot_fill`` reads those)
    for name in ("batches", "uniq_rows", "uniq_slots"):
        assert "pipeline/" + name not in fitted
        assert "pipeline/" + name not in raw
