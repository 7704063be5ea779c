#!/usr/bin/env python
"""Interleaved Pallas-vs-XLA A/B probe — regenerates the kernel matrix.

``kernel = auto`` follows the (L, dedup) regime matrix in
``ops/kernel_choice.py``. That matrix is an earlier device's
measurement, unverified on the v5e (ROADMAP D4); re-run this tool
there and, if the regime boundary moved, either update the matrix or
pin ``kernel = pallas|xla`` per job.

Each cell times the FULL jitted train step (gather + scorer + grad +
sparse Adagrad — the same executable training runs, not a bare scorer)
device-only on a resident batch, INTERLEAVING the two kernels inside
each trial: ambient throughput on a shared chip can swing from one
minute to the next, so only same-window ratios mean anything. The
per-cell verdict is the median of
per-trial ratios, with every sample printed.

Usage: python tools/kernel_probe.py [--k 8] [--B 8192]
       [--L 48,64] [--dedup device,host] [--steps 100] [--trials 5]
Prints one JSON object: per-cell rates, ratios, winner, and whether
auto (the shipped matrix) agrees with the measurement.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def time_kernel(step, make_state, args, steps):
    """One timed burst of the donated-step loop; returns examples/sec.
    ``make_state`` builds FRESH table/acc each burst — the step donates
    its state buffers, so a shared pair would be deleted after the
    first burst."""
    import jax
    B = args["labels"].shape[0]
    t, a = make_state()
    for _ in range(3):  # warm (compile is cached from the prior burst)
        t, a, _, _ = step(t, a, **args)
    jax.block_until_ready((t, a))
    t0 = time.perf_counter()
    for _ in range(steps):
        t, a, _, _ = step(t, a, **args)
    jax.block_until_ready((t, a))
    return steps * B / (time.perf_counter() - t0)


def probe_cell(L, dedup, k, B, steps, trials):
    """Median-of-trials interleaved A/B for one (L, dedup) cell."""
    import dataclasses

    import jax
    from bench import synth_lines
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.data.pipeline import batch_iterator
    from fast_tffm_tpu.models.fm import (ModelSpec, batch_args,
                                         init_accumulator, init_table,
                                         make_train_step)
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "probe.txt")
        with open(path, "w") as fh:
            fh.write("\n".join(synth_lines(B, 1 << 20)) + "\n")
        cfg = FmConfig(vocabulary_size=1 << 20, factor_num=k,
                       batch_size=B, max_features_per_example=L,
                       bucket_ladder=(L,), train_files=(path,),
                       dedup=dedup, shuffle=False)
        spec = ModelSpec.from_config(cfg)
        raw = spec.dedup == "device"
        batch = next(batch_iterator(cfg, cfg.train_files, training=True,
                                    raw_ids=raw))
    args = {k_: (jax.device_put(v) if v is not None else None)
            for k_, v in batch_args(batch).items()}

    def make_state():
        return init_table(cfg, 0), init_accumulator(cfg)

    steps_by = {kern: make_train_step(
        dataclasses.replace(spec, kernel=kern))
        for kern in ("pallas", "xla")}
    samples = {"pallas": [], "xla": []}
    for _ in range(trials):
        for kern in ("pallas", "xla"):  # interleaved: same window
            samples[kern].append(
                time_kernel(steps_by[kern], make_state, args, steps))
    med = {kern: statistics.median(v) for kern, v in samples.items()}
    # Verdict = median of PER-TRIAL ratios: each trial's pallas/xla
    # pair ran back-to-back in one ambient window, so its ratio is
    # comparable even when absolute rates swing 1.4-4x between trials;
    # a ratio of medians would mix windows.
    ratios = [p / x for p, x in zip(samples["pallas"], samples["xla"])]
    med_ratio = statistics.median(ratios)
    from fast_tffm_tpu.ops.kernel_choice import auto_kernel
    winner = "pallas" if med_ratio >= 1.0 else "xla"
    return {"L": L, "dedup": spec.dedup, "k": k, "B": B,
            "pallas": round(med["pallas"], 1),
            "xla": round(med["xla"], 1),
            "pallas_trials": [round(v, 1) for v in samples["pallas"]],
            "xla_trials": [round(v, 1) for v in samples["xla"]],
            "trial_ratios": [round(r, 3) for r in ratios],
            "ratio_pallas_over_xla": round(med_ratio, 3),
            "winner": winner,
            "auto_picks": auto_kernel(spec.dedup, L),
            "auto_agrees": auto_kernel(spec.dedup, L) == winner}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--B", type=int, default=8192)
    ap.add_argument("--L", default="48,64")
    ap.add_argument("--dedup", default="device,host")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--trials", type=int, default=5)
    args = ap.parse_args()
    import jax
    cells = [probe_cell(L, dd, args.k, args.B, args.steps, args.trials)
             for L in (int(x) for x in args.L.split(","))
             for dd in args.dedup.split(",")]
    print(json.dumps({"backend": jax.default_backend(),
                      "cells": cells,
                      "all_auto_agree": all(c["auto_agrees"]
                                            for c in cells)}))


if __name__ == "__main__":
    main()
