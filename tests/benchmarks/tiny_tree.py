"""A copy of the benchmark with tiny configurations, traffic files, a
per-layer metric, cells and the reference of a model family the
benchmark has none for, all ADDED AS FILES, and new entries in
BENCHMARK.json: what a later PR does, at a size the CPU holds. The
predict cell comes with its end-to-end metric and the per-layer
metrics whose files benchmarks/layer_metrics/ already holds: what the
`benchmark` PR that lands a predict cell adds (PERF.md, Open
questions)."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_CONFIG = {
    "name": "tiny-fm",
    "source": "test",
    "reduced": [],
    "reference_family": "fm_order2",
    "program": {
        "General": {"vocabulary_size": 4096, "hash_feature_id": True,
                    "factor_num": 4, "model_type": "fm"},
        "Train": {"batch_size": 64, "learning_rate": 0.05,
                  "factor_lambda": 1e-6, "bias_lambda": 1e-6,
                  "init_value_range": 0.01, "loss_type": "logistic"}},
    "features": {"numeric": 2, "categorical_cardinalities": [50, 7, 300],
                 "zipf_a": 1.35, "positive_rate": 0.3},
    "check_limits": {
        "train": {"loss_rel_gap_max": 1.2e-4,
                  "grad_norm_gap_worst_leaf": 5e-5,
                  "update_norm_gap_worst_leaf": 5e-5},
        "predict": {"score_abs_gap_max": 2e-5}},
}
TINY_FFM = dict(
    TINY_CONFIG, name="tiny-ffm", reference_family="ffm",
    program={"General": {"vocabulary_size": 4096, "hash_feature_id": True,
                         "factor_num": 2, "model_type": "ffm",
                         "field_num": 4},
             "Train": TINY_CONFIG["program"]["Train"]},
    features={"numeric": 1, "categorical_cardinalities": [50, 7, 300],
              "zipf_a": 1.35, "positive_rate": 0.3})
# FM of order 3 (the ANOVA kernels of degree 2 and 3 over one factor
# matrix): a family benchmarks/references/ has no file for, so the tree
# brings its own (ORDER3_REFERENCE). init_value_range is 0.3, not the
# 0.01 of the others: at 0.01 the degree-3 term is about 7e-3 of the
# gradient and, being uncorrelated with the rest, moves a leaf's norm
# by about 2.5e-5, UNDER the 5e-5 limit, so a reference that dropped it
# would pass; at 0.3 it stands far above every limit.
TINY_FM3 = dict(
    TINY_CONFIG, name="tiny-fm3", reference_family="fm_order3_tiny",
    program={"General": dict(TINY_CONFIG["program"]["General"], order=3),
             "Train": dict(TINY_CONFIG["program"]["Train"],
                           init_value_range=0.3)})
# The same configuration with the second-order file named: `order`
# and the family have to reach the check, so this is NOT correct.
TINY_FM3_WRONG = dict(TINY_FM3, name="tiny-fm3-order2-reference",
                      reference_family="fm_order2")
ORDER3_REFERENCE = '''"""FM of order 3 (Blondel et al. 2016): rows ``[v (k) | w]``, the ANOVA
kernels of degree 2 and 3 over ONE factor matrix, per factor column on
power sums (Newton's identities), with z_l = x_l v_l, p_m = sum_l z_l^m:

    A2 = (p1^2 - p2) / 2           dA2/dz_l = p1 - z_l
    A3 = (p1^3 - 3 p1 p2 + 2 p3)/6 dA3/dz_l = (p1^2 - p2)/2 - p1 z_l + z_l^2
    score = sum_l w_l x_l + sum_k (A2 + A3)

Independent of the program's recurrence over the feature slots."""

import numpy as np

from benchmarks.reference import quantize, scatter_rows


def row_dim(model):
    return int(model["factor_num"]) + 1


def scores_and_row_grads(model, P, inv, x, fields, quant=None):
    if int(model["order"]) != 3:
        raise ValueError("this is the reference of order 3")
    B, L = inv.shape
    U, D = P.shape
    rows = quantize(P, quant)[inv]
    xq = quantize(x, quant)
    w, v = rows[..., -1], rows[..., :-1]
    z = quantize(v * xq[..., None], quant)              # [B, L, k]
    p1 = quantize(z.sum(axis=1), quant)
    p2 = quantize(np.square(z).sum(axis=1), quant)
    p3 = quantize((z ** 3).sum(axis=1), quant)
    a2 = 0.5 * (np.square(p1) - p2)
    a3 = (p1 ** 3 - 3.0 * p1 * p2 + 2.0 * p3) / 6.0
    score = (w * xq).sum(axis=1) + (a2 + a3).sum(axis=-1)

    def backward(ds):
        dz = ((p1[:, None, :] - z)
              + (a2[:, None, :] - p1[:, None, :] * z + np.square(z)))
        g = np.empty((B, L, D))
        g[..., -1] = ds[:, None] * xq
        g[..., :-1] = ds[:, None, None] * xq[..., None] * dz
        return scatter_rows(inv, g, U)
    return score, backward
'''
TINY_TRAIN = {"kind": "train", "corpus_batches": 4, "corpus_files": 2,
              "corpus_passes": 2, "steps_per_reading": 4, "warmup_readings": 1,
              "checked_steps": 3, "trace_seconds": 0.3}
TINY_PREDICT = {"kind": "predict", "corpus_batches": 4, "corpus_files": 2,
                "corpus_passes": 2, "calls_per_reading": 1, "warmup_calls": 1,
                "checked_lines": 100, "table_value_range": 0.05,
                "trace_seconds": 0.3}
PREDICT_E2E = {"name": "predict_examples_per_s", "unit": "examples/s",
               "better": "higher", "bound": 0.05, "source": "host_clock",
               "workloads": []}
PREDICT_LAYER = ("steady_rate.predict", "score_device_ms",
                 "predict_host_share", "predict_idle_setup",
                 "predict_idle_input_wait", "predict_idle_score_dispatch",
                 "predict_idle_write_wait", "predict_idle_drain")
# traffic files of ``kind: predict``: the tree's own and the repo's
PREDICT_TRAFFIC = ("tiny-predict", "predict-sweep")
TINY_METRIC = {"name": "tiny_steps_per_s", "unit": "1/s",
               "better": "higher", "source": "program_counter",
               "layer": "device step (models/fm.py)",
               "moves": "train_examples_per_s_per_chip",
               "reader": "telemetry_window",
               "args": {"counter": "train/steps", "over": "wall_pct"}}


def _dump(path, obj):
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(obj, fh, indent=1)


def make(dst: str) -> str:
    """Build the tree under ``dst``; returns it. Nothing that was
    there is edited but BENCHMARK.json, which gains entries."""
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(dst, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    b = os.path.join(dst, "benchmarks")
    # Only a tree that lacks them gains the predict metrics: the repo's
    # file will hold them once its own predict cell lands.
    if not any(m["name"] == PREDICT_E2E["name"] for m in spec["end_to_end"]):
        spec["end_to_end"].append(dict(PREDICT_E2E, workloads=[]))
    have = {m["name"] for m in spec["per_layer"]}
    for name in PREDICT_LAYER:
        if name in have:
            continue
        with open(os.path.join(b, "layer_metrics", name + ".json")) as fh:
            own = json.load(fh)
        spec["per_layer"].append(
            {k: own[k] for k in ("name", "unit", "better", "source",
                                 "layer", "moves")} | {"workloads": []})
    for config in (TINY_CONFIG, TINY_FFM, TINY_FM3, TINY_FM3_WRONG):
        name = config["name"]
        _dump(os.path.join(b, "configs", name + ".json"), config)
        spec["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmarks/configs/{name}.json"})
    with open(os.path.join(b, "references", "fm_order3_tiny.py"), "w",
              encoding="utf-8") as fh:
        fh.write(ORDER3_REFERENCE)
    _dump(os.path.join(b, "traffic", "tiny-train.json"), TINY_TRAIN)
    _dump(os.path.join(b, "traffic", "tiny-predict.json"), TINY_PREDICT)
    _dump(os.path.join(b, "layer_metrics", "tiny_steps_per_s.json"),
          TINY_METRIC)
    cells = [("tiny-train", "tiny-fm", "tiny-train"),
             ("tiny-ffm-train", "tiny-ffm", "tiny-train"),
             ("tiny-predict", "tiny-fm", "tiny-predict"),
             ("tiny-fm3-train", "tiny-fm3", "tiny-train"),
             ("tiny-fm3-order2-reference-train",
              "tiny-fm3-order2-reference", "tiny-train"),
             # the repo's own predict traffic file on the tiny table
             ("tiny-predict-sweep", "tiny-fm", "predict-sweep")]
    for name, config, traffic in cells:
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": traffic, "chips": 1,
                                  "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kind = ("predict" if "predict" in m["name"]
                    or m["name"].startswith("score") else "train")
            m["workloads"] += [c[0] for c in cells
                               if (c[2] in PREDICT_TRAFFIC)
                               == (kind == "predict")]
    spec["per_layer"].append({
        k: TINY_METRIC[k] for k in ("name", "unit", "better", "source",
                                    "layer", "moves")}
        | {"workloads": ["tiny-train"]})
    _dump(os.path.join(dst, "BENCHMARK.json"), spec)
    return dst


def env():
    e = dict(os.environ)
    e["PYTHONPATH"] = REPO + os.pathsep + e.get("PYTHONPATH", "")
    e["JAX_PLATFORMS"] = "cpu"
    e.pop("XLA_FLAGS", None)            # one CPU device: the one-chip path
    return e


if __name__ == "__main__":
    import sys
    print(make(sys.argv[1]))
