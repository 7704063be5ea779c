"""A copy of the benchmark with one tiny configuration, traffic file,
per-layer metric and cell ADDED AS FILES, and one new entry each in
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
    TINY_CONFIG, name="tiny-ffm",
    program={"General": {"vocabulary_size": 4096, "hash_feature_id": True,
                         "factor_num": 2, "model_type": "ffm",
                         "field_num": 4},
             "Train": TINY_CONFIG["program"]["Train"]},
    features={"numeric": 1, "categorical_cardinalities": [50, 7, 300],
              "zipf_a": 1.35, "positive_rate": 0.3})
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
PREDICT_LAYER = ("score_device_ms", "predict_host_share",
                 "steady_rate.predict")
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
    spec["end_to_end"].append(dict(PREDICT_E2E))
    for name in PREDICT_LAYER:
        with open(os.path.join(b, "layer_metrics", name + ".json")) as fh:
            own = json.load(fh)
        spec["per_layer"].append(
            {k: own[k] for k in ("name", "unit", "better", "source",
                                 "layer", "moves")} | {"workloads": []})
    _dump(os.path.join(b, "configs", "tiny-fm.json"), TINY_CONFIG)
    _dump(os.path.join(b, "configs", "tiny-ffm.json"), TINY_FFM)
    _dump(os.path.join(b, "traffic", "tiny-train.json"), TINY_TRAIN)
    _dump(os.path.join(b, "traffic", "tiny-predict.json"), TINY_PREDICT)
    _dump(os.path.join(b, "layer_metrics", "tiny_steps_per_s.json"),
          TINY_METRIC)
    for name in ("tiny-fm", "tiny-ffm"):
        spec["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmarks/configs/{name}.json"})
    cells = [("tiny-train", "tiny-fm", "tiny-train"),
             ("tiny-ffm-train", "tiny-ffm", "tiny-train"),
             ("tiny-predict", "tiny-fm", "tiny-predict")]
    for name, config, traffic in cells:
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": traffic, "chips": 1,
                                  "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kind = ("predict" if "predict" in m["name"]
                    or m["name"].startswith("score") else "train")
            m["workloads"] += [c[0] for c in cells
                               if (c[2] == "tiny-predict")
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
