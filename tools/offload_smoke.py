#!/usr/bin/env python
"""Offload smoke: train a table that dwarfs device HBM via lookup=host.

BASELINE config #5's shape is a 10^9-row hashed FM whose table lives
outside device memory. This tool runs the same *structure* at a
configurable scale (default 10^8 rows ~= 3.6 GB table + 3.6 GB Adagrad
accumulator, vs ~16 GB device HBM on a v5 lite chip): synthesizes
hashed-id libsvm data, trains steps through the lookup.py offload seam
on the real chip, and prints a JSON accounting line proving where the
state lived —

- ``numpy`` backend: local host RSS covers table + accumulator; the
  device only ever holds the per-batch [U, D] blocks.
- ``pinned`` backend (the device-resident fast path): the state's jax
  shardings report ``memory_kind="pinned_host"`` (accelerator-host
  memory, NOT HBM, NOT local RAM — local RSS stays flat), and the whole
  step runs in-jit with no per-step Python round-trip.

Usage: python tools/offload_smoke.py [--rows 100000000] [--steps 20]
       [--backend auto|pinned|numpy]
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def synth_hashed_lines(n, seed=0):
    """Criteo-like lines with STRING feature ids (hash_feature_id path):
    39 features/example over an effectively unbounded id space."""
    rng = np.random.default_rng(seed)
    labels = (rng.random(n) < 0.25).astype(np.int32)
    # Zipf-ish ids: a dense head plus a huge tail, like real CTR data.
    head = rng.integers(0, 10_000, size=(n, 13))
    tail = rng.integers(0, 1 << 40, size=(n, 26))
    lines = []
    for i in range(n):
        parts = [str(labels[i])]
        parts += [f"f{j}_{head[i, j]}:1" for j in range(13)]
        parts += [f"c{j}_{tail[i, j]}:1" for j in range(26)]
        lines.append(" ".join(parts))
    return lines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=100_000_000)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--backend", choices=("auto", "pinned", "numpy"),
                    default="auto")
    args = ap.parse_args()

    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.lookup import (HostOffloadLookup, PinnedHostLookup,
                                      make_offload_backend,
                                      make_offload_train_step,
                                      memory_report)
    from fast_tffm_tpu.models.fm import ModelSpec, batch_args
    from fast_tffm_tpu.data.pipeline import batch_iterator

    # The CLI's persistent compile cache: without it the first step's
    # compile (tens of seconds on a TPU) lands inside whatever span
    # contains it and the recorded rates conflate compile/cache state
    # with steady-state throughput.
    from fast_tffm_tpu.compile_cache import (
        enable_compilation_cache)
    enable_compilation_cache()

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "train.txt")
        # +1 batch: the first step is an UNTIMED warmup (pays any
        # residual compile), so the timed loop still covers args.steps.
        with open(path, "w") as fh:
            fh.write("\n".join(
                synth_hashed_lines((args.steps + 1) * args.batch)) + "\n")

        cfg = FmConfig(vocabulary_size=args.rows, factor_num=8,
                       batch_size=args.batch, learning_rate=0.05,
                       hash_feature_id=True, lookup="host",
                       max_features_per_example=64, bucket_ladder=(64,),
                       train_files=(path,), shuffle=False)
        spec = ModelSpec.from_config(cfg)

        import jax
        baseline = memory_report()  # corpus transients already freed
        t0 = time.perf_counter()
        if args.backend == "pinned":
            lk = PinnedHostLookup(cfg, seed=0)
        elif args.backend == "numpy":
            lk = HostOffloadLookup(cfg, seed=0)
        else:
            lk = make_offload_backend(cfg, seed=0)
        # The pinned init dispatches its chunked fills asynchronously;
        # without a fence the fill EXECUTION would bleed into the
        # training span (understating init, deflating examples/sec).
        jax.block_until_ready((lk.table, lk.acc))
        init_s = time.perf_counter() - t0
        after_init = memory_report()

        step = make_offload_train_step(spec, lk, cfg.learning_rate)
        n_steps = 0
        n_examples = 0
        loss = None
        warm_s = None
        t0 = time.perf_counter()
        for batch in batch_iterator(cfg, cfg.train_files, training=True,
                                    epochs=1):
            loss, _ = step(**batch_args(batch))
            if warm_s is None:  # warmup step: compile + first dispatch
                jax.block_until_ready(loss)
                warm_s = time.perf_counter() - t0
                n_steps = 0
                n_examples = 0
                t0 = time.perf_counter()
                continue
            n_steps += 1
            n_examples += batch.num_real
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0

        rep = memory_report()
        table_gb = lk.rows * lk.dim * 4 / 2**30
        table_mb = table_gb * 1024
        pinned = isinstance(lk, PinnedHostLookup)
        mode = getattr(lk, "mode", "numpy")
        out = {
            "backend": type(lk).__name__,
            "mode": mode,
            "rows": lk.rows, "row_dim": lk.dim,
            "table_gb": round(table_gb, 2),
            "state_gb": round(2 * table_gb, 2),
            "init_sec": round(init_s, 1),
            "warmup_sec": round(warm_s or 0.0, 1),
            "steps": n_steps, "examples": n_examples,
            "examples_per_sec": round(n_examples / dt, 1),
            "final_loss": round(float(loss), 6),
            "host_rss_mb_baseline": baseline["host_rss_mb"],
            "host_rss_mb_after_init": after_init["host_rss_mb"],
            "host_rss_mb": rep["host_rss_mb"],
            "device_in_use_mb": rep["device_in_use_mb"],
            "device_limit_mb": rep["device_limit_mb"],
            "platform": jax.default_backend(),
        }
        if pinned:
            out["table_memory_kind"] = lk.table.sharding.memory_kind
            out["acc_memory_kind"] = lk.acc.sharding.memory_kind
        print(json.dumps(out))

        # The accounting claims, per backend. host_rss_mb is CURRENT
        # RSS and the bounds are BASELINE-RELATIVE, so the checks stay
        # meaningful at small --rows and don't bill freed transients.
        grew = rep["host_rss_mb"] - baseline["host_rss_mb"]
        if pinned and mode == "pinned":
            # State in accelerator-host memory: the shardings say so,
            # and LOCAL RAM must not have grown by anything near one
            # table copy.
            assert out["table_memory_kind"] == "pinned_host", out
            assert out["acc_memory_kind"] == "pinned_host", out
            assert grew < max(0.25 * table_mb, 512), \
                f"state appears to live in LOCAL RAM: +{grew} MB {rep}"
            # Peak-relative too: a regression that STAGES the full
            # table through local RAM during init and frees it would
            # pass the current-RSS bound; the chunked on-device init
            # exists precisely so no such copy ever materializes.
            peak_grew = (rep["host_peak_rss_mb"]
                         - baseline["host_peak_rss_mb"])
            assert peak_grew < max(0.5 * table_mb, 1024), \
                f"a transient table-sized copy crossed LOCAL RAM: " \
                f"+{peak_grew} MB peak {rep}"
        else:
            # numpy backend — and the pinned class in 'plain' mode
            # (CPU fallback), where device memory IS host RAM: local
            # RSS must have grown by ~the 2x-table state.
            assert grew > 2 * table_mb * 0.9, (grew, rep)
        dev = rep["device_in_use_mb"]
        if dev is not None:  # None = runtime reports no stats: UNMEASURED
            assert dev < 1024, f"table leaked onto the device: {rep}"


if __name__ == "__main__":
    main()
