"""The control of "How correct is decided": the reference put in the
program's place, computed in bfloat16 (the nearest precision below the
float32 the configurations state), compared with the float64 reference
by the same numbers a run compares. It must come out NOT correct.

    python3 -m benchmarks.control --workload <name> --seeds 1,2,3

Host only (NumPy, and the program's own reading of the cell's INI
sections for ``harness.model_of``): it needs no chip and reads the same
anywhere. The benchmark's own runs never run it; tests/benchmarks keeps
it at a small size, and PERF.md gives its readings at each cell's own
size."""

from __future__ import annotations

import argparse
import json
import os
import tempfile

import numpy as np

from benchmarks import check, corpus as corpus_mod, harness, reference, weights


def control_numbers(cell: harness.Cell, seed: int, work: str) -> dict:
    prog, tr = cell.config["program"], cell.traffic
    gen, trn = prog["General"], prog["Train"]
    batch = int(trn["batch_size"])
    vocab = int(gen["vocabulary_size"])
    model = harness.model_of(harness.program_cfg(cell.config, {}, work),
                             cell.config)
    dim = model["row_dim"]
    c = corpus_mod.generate(cell.config["features"], model["model_type"],
                            vocab, int(tr["corpus_batches"]) * batch, seed,
                            work, int(tr["corpus_files"]), "control")
    rows_n = vocab + 1
    if tr["kind"] == "predict":
        vr = float(tr["table_value_range"])
        rng = np.random.default_rng([int(seed), 0x5A3B1E])
        sample = np.unique(rng.integers(0, len(c.labels),
                                        size=int(tr["checked_lines"])))
        ref = check.reference_scores(model, rows_n, vr, seed, c, sample)
        low = check.reference_scores(model, rows_n, vr, seed, c, sample,
                                     quant="bf16")
        # what predict() writes: %.6f text
        return {"score_abs_gap_max": float(np.abs(
            np.round(low, 6) - ref).max())}
    vr = float(trn["init_value_range"])
    n = int(tr["checked_steps"])
    batches = [(c.rows[i * batch:(i + 1) * batch],
                c.vals[i * batch:(i + 1) * batch],
                c.labels[i * batch:(i + 1) * batch].astype(np.float64),
                np.ones(batch)) for i in range(n)]
    rows_all = np.unique(np.concatenate(
        [b[0].ravel() for b in batches] + [np.array([vocab])]))
    t0 = weights.table_rows_numpy(rows_all, dim, seed, vr, rows_n)
    out = {}
    runs = {}
    for quant in (None, "bf16"):
        tr_ = reference.ReferenceTrainer(model, rows_all, t0, quant)
        losses, g1 = [], None
        for i, (r, x, y, w) in enumerate(batches):
            losses.append(tr_.step(r, x, y, w, c.fields))
            if i == 0:
                g1 = np.zeros_like(tr_.table)
                g1[tr_.last_touched] = tr_.last_grad
        runs[quant] = (losses, g1, tr_.table - tr_.table0)
    (l0, g0, d0), (l1, g1, d1) = runs[None], runs["bf16"]
    out["loss_rel_gap_max"] = max(abs(a - b) / abs(b)
                                  for a, b in zip(l1, l0))
    out["grad_norm_gap_worst_leaf"] = reference.leaf_norm_gaps(
        g1, g0)["worst"]
    out["update_norm_gap_worst_leaf"] = reference.leaf_norm_gaps(
        d1, d0)["worst"]
    # The faults the numbers a lower precision hardly moves are held
    # against: half the batch left out (the loss), a step that returns
    # its state unchanged (the norm of the parameters' change).
    r, x, y, w = batches[0]
    half = w.copy()
    half[: len(half) // 2] = 0
    th = reference.ReferenceTrainer(model, rows_all, t0)
    lh = th.step(r, x, y, half, c.fields)
    gh = np.zeros_like(th.table)
    gh[th.last_touched] = th.last_grad
    out["fault_half_batch_loss_rel_gap"] = abs(lh - l0[0]) / abs(l0[0])
    out["fault_half_batch_grad_gap"] = reference.leaf_norm_gaps(
        gh, g0)["worst"]
    out["fault_unchanged_state_update_gap"] = reference.leaf_norm_gaps(
        np.zeros_like(d0), d0)["worst"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    a = ap.parse_args(argv)
    cell = harness.load_cell(a.workload)
    limits = cell.config["check_limits"][cell.kind]
    for seed in (int(s) for s in a.seeds.split(",")):
        with tempfile.TemporaryDirectory(
                dir=os.environ.get("TMPDIR")) as work:
            nums = control_numbers(cell, seed, work)
        failed = [k for k, v in nums.items()
                  if k in limits and v > limits[k]]
        print(json.dumps({"workload": a.workload, "seed": seed,
                          "control": nums, "limits": limits,
                          "fails": failed,
                          "correct": not failed}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
