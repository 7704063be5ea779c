"""tiny_tree.py's tree with a cell of ``kind: train_eval`` added AS
FILES: the tiny FM configuration with limits for the sweep's checks, a
traffic file whose held-out set is three batches swept after every
epoch of eight steps, and the per-layer metrics the repo's cell adds.
The driver, the AUC's reference and the readers come with the repo's
``benchmarks/``: the tree runs them unedited."""

import json
import os

import tiny_tree

REPO = tiny_tree.REPO
CELL = "tiny-train-eval"
TINY_EVAL = dict(
    tiny_tree.TINY_CONFIG, name="tiny-fm-eval",
    check_limits=dict(tiny_tree.TINY_CONFIG["check_limits"],
                      train_eval={"score_abs_gap_max": 2e-5,
                                  "auc_binned_abs_gap_max": 1e-3}))
TINY_TRAFFIC = {"kind": "train_eval", "corpus_batches": 4, "corpus_files": 2,
                "corpus_passes": 2, "heldout_batches": 3, "heldout_files": 2,
                "steps_per_reading": 4, "warmup_readings": 3,
                "checked_steps": 3, "checked_score_calls": 2,
                "trace_seconds": 0.3}


def metrics() -> list:
    """The per-layer metrics the repo lists for its own such cell and
    for no other."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    return [m["name"] for m in spec["per_layer"]
            if m.get("workloads") == ["fm16-train-eval"]]


def make(dst: str) -> str:
    """tiny_tree.make(dst), then the cell's files and entries."""
    tiny_tree.make(dst)
    b = os.path.join(dst, "benchmarks")
    tiny_tree._dump(os.path.join(b, "configs", "tiny-fm-eval.json"),
                    TINY_EVAL)
    tiny_tree._dump(os.path.join(b, "traffic", "tiny-train-eval.json"),
                    TINY_TRAFFIC)
    with open(os.path.join(dst, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    spec["configs"].append({
        "name": "tiny-fm-eval", "source": "test", "reduced": [],
        "why": "test", "file": "benchmarks/configs/tiny-fm-eval.json"})
    spec["workloads"].append({"name": CELL, "config": "tiny-fm-eval",
                              "traffic": "tiny-train-eval", "chips": 1,
                              "why": "test"})
    own = metrics()
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny-train" in m.get("workloads", ()) or m["name"] in own:
            m["workloads"].append(CELL)
    tiny_tree._dump(os.path.join(dst, "BENCHMARK.json"), spec)
    return dst


if __name__ == "__main__":
    import sys
    print(make(sys.argv[1]))
