"""tiny_tree.py's tree with a cell of ``kind: train_bags`` added AS
FILES: a tiny configuration whose lines have three ids and two bags of
words (3 to 21 cells a line, so that a batch of 64 ships at the 16 or
the 24 rung of the width ladder and a job holds two step programs), a
traffic file and the three per-layer metrics the repo's cell reports.
The generator, the driver and the control come with the repo's
``benchmarks/``: the tree runs them unedited."""

import json
import os

import tiny_tree

REPO = tiny_tree.REPO
CELL = "tiny-bags-train"
LIMITS = {"loss_rel_gap_max": 1.2e-4, "grad_norm_gap_worst_leaf": 5e-5,
          "update_norm_gap_worst_leaf": 5e-5}
TINY_BAGS = dict(
    tiny_tree.TINY_CONFIG, name="tiny-fm-bags",
    features={"id_cardinalities": [50, 7, 300], "zipf_a": 1.35,
              "bag_vocabulary": 512,
              "bags": [{"name": "query", "median": 2, "sigma": 0.7,
                        "cap": 6},
                       {"name": "title", "median": 4, "sigma": 0.4,
                        "cap": 12}],
              "positive_rate": 0.3},
    check_limits={"train": LIMITS, "train_bags": LIMITS})
TINY_TRAFFIC = {"kind": "train_bags", "corpus_batches": 8, "corpus_files": 2,
                "corpus_passes": 1, "steps_per_reading": 4,
                "warmup_readings": 6, "checked_steps": 3,
                "checked_widths": 2, "checked_steps_most": 24,
                "trace_seconds": 0.3}
METRICS = ("cells_per_example", "program_switches_per_step",
           "truncated_cells_per_example")


def make(dst: str) -> str:
    """tiny_tree.make(dst), then the bags cell's files and entries."""
    tiny_tree.make(dst)
    b = os.path.join(dst, "benchmarks")
    tiny_tree._dump(os.path.join(b, "configs", "tiny-fm-bags.json"),
                    TINY_BAGS)
    tiny_tree._dump(os.path.join(b, "traffic", "tiny-bags.json"),
                    TINY_TRAFFIC)
    with open(os.path.join(dst, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    spec["configs"].append({
        "name": "tiny-fm-bags", "source": "test", "reduced": [],
        "why": "test", "file": "benchmarks/configs/tiny-fm-bags.json"})
    spec["workloads"].append({"name": CELL, "config": "tiny-fm-bags",
                              "traffic": "tiny-bags", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny-train" in m.get("workloads", ()) or m["name"] in METRICS:
            m["workloads"].append(CELL)
    tiny_tree._dump(os.path.join(dst, "BENCHMARK.json"), spec)
    return dst


if __name__ == "__main__":
    import sys
    print(make(sys.argv[1]))
