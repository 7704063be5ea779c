"""tiny_tree.py's tree with cells of ``kind: train_stream`` added AS
FILES: the tiny FM configuration with the README's stream settings and
limits for the stream's checks, the repo's own traffic file
(``stream-catchup``: 1,024 sealed shards, here of 8 x 16 lines) and a
shorter one for the planted faults. The driver, the reference and the
readers come with the repo's ``benchmarks/``: the tree runs them
unedited."""

import json
import os

import tiny_tree

REPO = tiny_tree.REPO
CELL = "tiny-stream-catchup"        # the repo's traffic file
SHORT = "tiny-stream-short"         # the faults': shards of 2 batches
STREAM_CHECKS = ("stream_batches_not_in_ledger_order",
                 "stream_lines_trained_twice_or_never",
                 "watermark_lines_off", "stream_idle_in_span")
# batch_size 16, not the tree's 64: the repo's traffic file lists 1,024
# shards, and one read round holds them all at this size (a round's
# chunks are appended to the scan buffer one by one: PERF.md, P10).
TINY_STREAM = dict(
    tiny_tree.TINY_CONFIG, name="tiny-fm-stream",
    program={"General": tiny_tree.TINY_CONFIG["program"]["General"],
             "Train": dict(tiny_tree.TINY_CONFIG["program"]["Train"],
                           batch_size=16,
                           run_mode="stream", stream_dir="stream",
                           stream_poll_seconds=2, seal_policy="done",
                           publish_interval_seconds=300)},
    check_limits=dict(
        tiny_tree.TINY_CONFIG["check_limits"],
        train_stream=dict(tiny_tree.TINY_CONFIG["check_limits"]["train"],
                          **dict.fromkeys(STREAM_CHECKS, 0))))
SHORT_TRAFFIC = {"kind": "train_stream", "corpus_batches": 8,
                 "corpus_files": 4, "backlog_passes": 256,
                 "steps_per_reading": 4, "warmup_readings": 5,
                 "checked_steps": 3, "checked_stream_batches": 20,
                 "trace_seconds": 0.3}


def metrics() -> list:
    """The per-layer metrics the repo lists for its own such cell and
    for no other."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    return [m["name"] for m in spec["per_layer"]
            if m.get("workloads") == ["fm16-stream-catchup"]]


def make(dst: str) -> str:
    """tiny_tree.make(dst), then the cells' files and entries."""
    tiny_tree.make(dst)
    b = os.path.join(dst, "benchmarks")
    tiny_tree._dump(os.path.join(b, "configs", "tiny-fm-stream.json"),
                    TINY_STREAM)
    tiny_tree._dump(os.path.join(b, "traffic", SHORT + ".json"),
                    SHORT_TRAFFIC)
    with open(os.path.join(dst, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    spec["configs"].append({
        "name": "tiny-fm-stream", "source": "test", "reduced": [],
        "why": "test", "file": "benchmarks/configs/tiny-fm-stream.json"})
    cells = [(CELL, "stream-catchup"), (SHORT, SHORT)]
    for name, traffic in cells:
        spec["workloads"].append({"name": name, "config": "tiny-fm-stream",
                                  "traffic": traffic, "chips": 1,
                                  "why": "test"})
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        repo = json.load(fh)
    # on every list the repo's own stream cell is on, and on no other
    listed = {m["name"] for m in repo["end_to_end"] + repo["per_layer"]
              if "fm16-stream-catchup" in m.get("workloads", ())}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in listed:
            m["workloads"] += [c[0] for c in cells]
    tiny_tree._dump(os.path.join(dst, "BENCHMARK.json"), spec)
    return dst


if __name__ == "__main__":
    import sys
    print(make(sys.argv[1]))
