"""tiny_tree.py's tree with a cell of ``kind: train_save`` added AS
FILES: the tiny FM configuration with ``save_steps`` and the save's
limits, a traffic file whose one save falls an epoch into the window,
and the per-layer metrics the repo's cell adds. The driver, the save's
reference and the readers come with the repo's ``benchmarks/``: the
tree runs them unedited."""

import json
import os

import tiny_tree

REPO = tiny_tree.REPO
CELL = "tiny-train-save"
SAVE_STEPS = 2048
SAVE_LIMITS = {k: 0 for k in (
    "saved_rows_not_of_step", "saved_untouched_rows_off",
    "saved_scalars_off", "manifest_mismatches", "saves_in_span_not_one",
    "save_off_schedule")}
TINY_SAVE = dict(
    tiny_tree.TINY_CONFIG, name="tiny-fm-save",
    program={"General": tiny_tree.TINY_CONFIG["program"]["General"],
             "Train": dict(tiny_tree.TINY_CONFIG["program"]["Train"],
                           save_steps=SAVE_STEPS, ckpt_verify="size")},
    check_limits=dict(tiny_tree.TINY_CONFIG["check_limits"],
                      train_save=SAVE_LIMITS))
# Epochs of 8 steps; the window opens at step 2,040, the save of step
# 2,048 falls one epoch into it and the next (4,096) past a window of
# a second or a second and a half on this table (a CPU steps it 250 to
# 600 times a second; 1,370 would reach the next save).
TINY_TRAFFIC = {"kind": "train_save", "corpus_batches": 4, "corpus_files": 2,
                "corpus_passes": 2, "save_steps": SAVE_STEPS,
                "saves_in_window": 1, "steps_per_reading": 4,
                "warmup_readings": 510, "checked_steps": 3,
                "untouched_rows_sampled": 2048, "trace_seconds": 0.3}


def metrics() -> list:
    """The per-layer metrics the repo lists for its own such cell and
    for no other."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    return [m["name"] for m in spec["per_layer"]
            if m.get("workloads") == ["fm16-train-save"]]


def make(dst: str) -> str:
    """tiny_tree.make(dst), then the cell's files and entries."""
    tiny_tree.make(dst)
    b = os.path.join(dst, "benchmarks")
    tiny_tree._dump(os.path.join(b, "configs", "tiny-fm-save.json"),
                    TINY_SAVE)
    tiny_tree._dump(os.path.join(b, "traffic", "tiny-train-save.json"),
                    TINY_TRAFFIC)
    with open(os.path.join(dst, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    spec["configs"].append({
        "name": "tiny-fm-save", "source": "test", "reduced": [],
        "why": "test", "file": "benchmarks/configs/tiny-fm-save.json"})
    spec["workloads"].append({"name": CELL, "config": "tiny-fm-save",
                              "traffic": "tiny-train-save", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny-train" in m.get("workloads", ()) and CELL not in m[
                "workloads"]:
            m["workloads"].append(CELL)
    tiny_tree._dump(os.path.join(dst, "BENCHMARK.json"), spec)
    return dst


if __name__ == "__main__":
    import sys
    print(make(sys.argv[1]))
