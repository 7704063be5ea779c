"""Benchmark: end-to-end training throughput on the flagship FM config,
with an attributable breakdown.

Mirrors BASELINE config #1 shapes (2nd-order FM, k=8, Criteo-Kaggle-like
data: ~39 features/example, 1M-row hash space) on whatever single device
is present. NOTHING here has been measured on the current chip: the
rewrite that stamps the device into every number and refuses to run
without a TPU is ROADMAP S1; chip_smoke.py is what runs there today.

The headline metric is the median of ``TRIALS`` end-to-end runs of the
full training loop — host text parsing (C++ parser), batch building/
dedup, host->device transfer, and the jitted train step — i.e. the same
end-to-end examples/sec the reference's ``sess.run`` loop measures.
Because one bare number proved undiagnosable when it moved between
rounds, the same JSON carries the attribution breakdown:

- ``e2e_trials``: every end-to-end trial (spread = environment noise),
- ``host_only``: pipeline-only rate (file -> C++ parse -> dedup -> padded
  batch, device never touched) — the input-bound ceiling, measured at
  the e2e-chosen ``host_threads``; ``host_only_workers`` carries the
  1/2/4-worker sweep of the parallel host data plane (also standalone:
  ``python bench.py --host-sweep`` / ``make bench-host``),
- ``device_only``: jitted-step rate on one cached resident batch (no host
  work, no transfer) — the compute-bound ceiling,
- ``h2d_only``: device_put rate for one batch's actual payload (raw-ids
  mode ships ids+vals, ~3 MB/step at L=48) — the transfer ceiling,
- ``sharded_input_per_worker``: host-only rate of ONE of 2 byte-range
  shards (the multi-process fast path's per-worker input build),
  recorded so the "sharded input ~matches unsharded" claim is an
  artifact, not a commit message,
- ``ffm_e2e``: end-to-end rate of the field-aware model (BASELINE
  config #3 shapes: Avazu-like ~24 fields, k=4) through the same C++
  fast path — FFM's own bench line,
- ``order3_e2e``: end-to-end rate of the order-3 ANOVA-kernel FM
  (BASELINE config #4 shapes) — the higher-order capability's line,
- ``hashed_e2e``: end-to-end rate with ``hash_feature_id`` on (configs
  #2/#5 hash string ids; the headline uses plain int ids),
- ``predict_e2e``: batch-scoring rate through the real predict path
  (the reference's second workload: parse keep_empty -> score ->
  ordered scores),
- ``l64_e2e``: the DEFAULT production regime (auto ladder -> L=64 for
  Criteo-39 data; a one-chip train step takes the host unique, so
  kernel auto -> XLA there as at the headline's hand-tuned L=48) —
  this line documents the default path.

Every e2e line (headline, ffm, order3, hashed, predict, k16, l64) is the median of TRIALS
runs with the per-trial values alongside: a single late-in-the-run
trial read 8x low on an earlier device, and the medians make that
attributable instead of alarming.

Whichever of host_only/device_only sits near the e2e number names the
bottleneck; a regression that moves e2e but neither ceiling is noise.

Prints ONE JSON line:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ..., ...}

vs_baseline: BASELINE.json publishes no reference numbers ("published":
{}); the only stated target is the north star of 1e9 examples/hour on a
v5e-64 slice == 1e9/3600/64 ~= 4340 examples/sec/chip. vs_baseline is
value / 4340 — i.e. >= 1.0 means this single chip sustains its share of
the north-star rate.
"""

import json
import os
import statistics
import time

import numpy as np

NORTH_STAR_PER_CHIP = 1e9 / 3600.0 / 64.0  # examples/sec/chip


def _parse_threads() -> int:
    """The C++ builder's NATIVE feed parse-thread count — a different
    axis from the pipeline's ``host_threads`` build workers. Earlier
    rounds reported this value AS ``host_threads``, which
    made the artifact claim a build parallelism the pipeline didn't
    have; the JSON now carries both, correctly named."""
    from fast_tffm_tpu.data import cparser
    return cparser.auto_threads()


def _with_workers(cfg, host_threads):
    """The same bench config at an explicit data-plane worker count."""
    import dataclasses
    return dataclasses.replace(cfg, host_threads=host_threads)


# The parallel-plane sweep points: 1 (the serial pre-parallel path),
# 2, and 4 (the auto cap).
HOST_WORKER_SWEEP = (1, 2, 4)

B = 8192
N_WARM, N_TIMED = 4, 40
TRIALS = 3


def synth_lines(n, vocab, seed=0):
    """Criteo-like libsvm lines: 39 features (13 numeric-ish ids with
    values + 26 one-hot categorical ids), ids spread over the hash space."""
    rng = np.random.default_rng(seed)
    labels = (rng.random(n) < 0.25).astype(np.int32)
    num_ids = rng.integers(0, 13, size=(n, 13)) * 997 % vocab
    num_vals = np.round(rng.gamma(1.0, 2.0, size=(n, 13)), 2)
    cat_ids = rng.integers(0, vocab, size=(n, 26))
    lines = []
    for i in range(n):
        parts = [str(labels[i])]
        parts += [f"{num_ids[i, j]}:{num_vals[i, j]}" for j in range(13)]
        parts += [f"{cat_ids[i, j]}:1" for j in range(26)]
        lines.append(" ".join(parts))
    return lines


def make_cfg(path):
    from fast_tffm_tpu.config import FmConfig
    # L=48 covers Criteo's 39 features with the least padding; it won
    # over 64 on an earlier device where the loop was H2D-bound (record
    # removed in PR 21). The DEFAULT ladder puts this data at L=64 (the
    # Pallas cell only under an explicit dedup = device) —
    # chip_smoke.py runs that width; ROADMAP S1/D8.
    return FmConfig(vocabulary_size=1 << 20, factor_num=8, batch_size=B,
                    learning_rate=0.05, factor_lambda=1e-6,
                    bias_lambda=1e-6, max_features_per_example=48,
                    bucket_ladder=(48,), train_files=(path,),
                    shuffle=False)


def _raw_mode(cfg):
    """Whether the resolved TRAIN spec ships raw ids (an explicit
    dedup = device; auto gives a one-chip train step the host unique)
    — the pipeline must build matching batches."""
    from fast_tffm_tpu.models.fm import ModelSpec
    return ModelSpec.from_config(cfg, training=True).dedup == "device"


def _wire_dispatch(cfg, step):
    """The bench's train-step dispatch, routed through the wire layer
    exactly as train() routes it (README "Wire format"): encode ->
    explicit async device_put (the depth-2 double buffer) -> padded or
    packed jitted step. One body for run_e2e and the --wire sweep so
    the measured loop cannot drift from the production dispatch."""
    import jax
    from fast_tffm_tpu.models.fm import ModelSpec, make_packed_train_step
    from fast_tffm_tpu.wire import WireEncoder, resolve_wire
    wire = resolve_wire(cfg, train=True)
    enc = WireEncoder(wire, pad_id=cfg.pad_id)
    if wire.packed:
        pstep = make_packed_train_step(
            ModelSpec.from_config(cfg, training=True))

        def dispatch(table, acc, batch):
            wb = enc.encode_train(batch)
            return pstep(wb.L, table, acc, **jax.device_put(wb.args))
    else:
        def dispatch(table, acc, batch):
            wb = enc.encode_train(batch)
            return step(table, acc, **jax.device_put(wb.args))
    return dispatch


def run_e2e(cfg, step, n_warm=N_WARM, vocab=None):
    """One honest end-to-end trial: file -> C++ parse -> build -> wire
    encode -> H2D -> jitted step, host pipeline prefetching ahead of
    the device (the same loop train() runs; dedup runs host- or
    device-side per the resolved spec, and the dispatch routes through
    the wire layer, like train() does). One timing protocol for every
    e2e line (FM headline and FFM). ``vocab`` (the --vocab line): the
    admission runtime, exercised exactly as train() does — remap in
    the pipeline, note_trained per stepped batch."""
    import jax
    from fast_tffm_tpu.data.pipeline import (batch_iterator,
                                             gil_bound_iteration, prefetch)
    from fast_tffm_tpu.models.fm import init_accumulator, init_table
    table = init_table(cfg, 0)
    acc = init_accumulator(cfg)
    dispatch = _wire_dispatch(cfg, step)
    it = prefetch(batch_iterator(cfg, cfg.train_files, training=True,
                                 raw_ids=_raw_mode(cfg), vocab=vocab),
                  depth=4, gil_bound=gil_bound_iteration(cfg))
    t0 = None
    n = 0
    n_real = 0  # real examples in the timed span (short final batch counts
    # its actual rows, not batch_size)
    for batch in it:
        table, acc, loss, _ = dispatch(table, acc, batch)
        if vocab is not None:
            vocab.note_trained(batch)
        n += 1
        if t0 is not None:
            n_real += batch.num_real
        if n == n_warm:  # compile + cache warm; start the clock
            jax.block_until_ready((table, acc))
            t0 = time.perf_counter()
    if t0 is None or n_real == 0:
        raise ValueError(
            f"run_e2e needs more than n_warm={n_warm} batches to time "
            f"anything; the input yielded {n}")
    jax.block_until_ready((table, acc))
    return n_real / (time.perf_counter() - t0)


def run_host_only(cfg, shard_index=0, num_shards=1, raw_ids=None):
    """Pipeline-only rate: consume every batch, never touch the device.
    Defaults to the same raw/dedup build mode the e2e loop uses;
    sharded callers pass raw_ids=False (multi-process mode requires the
    host-dedup build, so that metric must measure it)."""
    from fast_tffm_tpu.data.pipeline import batch_iterator
    if raw_ids is None:
        raw_ids = _raw_mode(cfg)
    n_ex = 0
    t0 = time.perf_counter()
    for batch in batch_iterator(cfg, cfg.train_files, training=True,
                                shard_index=shard_index,
                                num_shards=num_shards, raw_ids=raw_ids):
        n_ex += batch.num_real
    return n_ex / (time.perf_counter() - t0)


def run_device_only(cfg, step):
    """Jitted-step rate on one device-resident batch: no host pipeline,
    no transfer. The batch args are device arrays reused every call
    (table/acc are donated and threaded through)."""
    import jax
    from fast_tffm_tpu.data.pipeline import batch_iterator
    from fast_tffm_tpu.models.fm import (batch_args, init_accumulator,
                                         init_table)
    batch = next(batch_iterator(cfg, cfg.train_files, training=True,
                                raw_ids=_raw_mode(cfg)))
    args = {k: (jax.device_put(v) if v is not None else None)
            for k, v in batch_args(batch).items()}
    table = init_table(cfg, 0)
    acc = init_accumulator(cfg)
    for _ in range(N_WARM):
        table, acc, loss, _ = step(table, acc, **args)
    jax.block_until_ready((table, acc))
    t0 = time.perf_counter()
    for _ in range(N_TIMED):
        table, acc, loss, _ = step(table, acc, **args)
    jax.block_until_ready((table, acc))
    return N_TIMED * B / (time.perf_counter() - t0)


def synth_ffm_lines(n, vocab, field_num=24, seed=0):
    """Avazu-like FFM lines: one categorical feature per field."""
    rng = np.random.default_rng(seed)
    labels = (rng.random(n) < 0.17).astype(np.int32)
    ids = rng.integers(0, vocab, size=(n, field_num))
    lines = []
    for i in range(n):
        toks = [f"{f}:{ids[i, f]}" for f in range(field_num)]
        lines.append(" ".join([str(labels[i])] + toks))
    return lines


def ffm_cfg(tmp):
    from fast_tffm_tpu.config import FmConfig
    return FmConfig(vocabulary_size=1 << 18, factor_num=4, batch_size=4096,
                    model_type="ffm", field_num=24, learning_rate=0.05,
                    factor_lambda=1e-6, bias_lambda=1e-6,
                    max_features_per_example=32, bucket_ladder=(32,),
                    train_files=(os.path.join(tmp, "ffm.txt"),),
                    shuffle=False)


def run_ffm_e2e(tmp):
    """FFM end-to-end trials (config #3 shapes), same timing protocol as
    the headline (run_e2e). Returns TRIALS rates: the first full bench
    run showed a single late-in-the-run trial can read 8x low (an
    earlier device: order3 138k in-run vs 880-938k re-run in
    isolation), so every e2e line gets the headline's median-of-trials treatment —
    post-compile trials cost ~0.4 s each."""
    from fast_tffm_tpu.models.fm import ModelSpec, make_train_step
    B_ffm, n_warm, n_timed = 4096, 3, 12
    cfg = ffm_cfg(tmp)
    with open(cfg.train_files[0], "w") as fh:
        fh.write("\n".join(synth_ffm_lines((n_warm + n_timed) * B_ffm,
                                           1 << 18)) + "\n")
    step = make_train_step(ModelSpec.from_config(cfg, training=True))
    return [run_e2e(cfg, step, n_warm=n_warm) for _ in range(TRIALS)]


def order3_cfg(tmp):
    from fast_tffm_tpu.config import FmConfig
    return FmConfig(vocabulary_size=1 << 20, factor_num=8, order=3,
                    batch_size=4096, learning_rate=0.05,
                    factor_lambda=1e-6, bias_lambda=1e-6,
                    max_features_per_example=48, bucket_ladder=(48,),
                    train_files=(os.path.join(tmp, "train.txt"),),
                    shuffle=False)


def run_order3_e2e(tmp):
    """Order-3 FM end-to-end trials (config #4 shapes), same timing
    protocol and median-of-trials treatment as the headline (see
    run_ffm_e2e on why). Reuses the FM data file already in ``tmp``."""
    from fast_tffm_tpu.models.fm import ModelSpec, make_train_step
    cfg = order3_cfg(tmp)
    step = make_train_step(ModelSpec.from_config(cfg, training=True))
    return [run_e2e(cfg, step, n_warm=3) for _ in range(TRIALS)]


def run_k16(cfg16):
    """BASELINE config #2's model shape (2nd-order FM, k=16): e2e trials
    plus the device-only Pallas-vs-XLA pair — the round-3 kernel claim
    (2.9x at k=8) was never validated at this k (round-3 review, weak #6).
    Reuses the headline data file via ``cfg16``."""
    import dataclasses
    from fast_tffm_tpu.models.fm import ModelSpec, make_train_step
    spec = ModelSpec.from_config(cfg16, training=True)
    step = make_train_step(spec)
    e2e = [run_e2e(cfg16, step, n_warm=3) for _ in range(TRIALS)]
    dev = {}
    for kern in ("pallas", "xla"):
        kspec = dataclasses.replace(spec, kernel=kern)
        dev[kern] = run_device_only(cfg16, make_train_step(kspec))
    return e2e, dev


def run_h2d_only(cfg):
    """Transfer-only rate: device_put one batch's WIRE payload per step
    (the per-step H2D traffic the resolved wire format actually ships —
    padded rectangles by default, flat CSR under wire_format = packed),
    nothing else. Also returns the payload bytes so the --wire sweep
    can report bytes/example beside the rate."""
    import jax
    from fast_tffm_tpu.data.pipeline import batch_iterator
    from fast_tffm_tpu.wire import WireEncoder, resolve_wire
    batch = next(batch_iterator(cfg, cfg.train_files, training=True,
                                raw_ids=_raw_mode(cfg)))
    enc = WireEncoder(resolve_wire(cfg, train=True), pad_id=cfg.pad_id)
    wb = enc.encode_train(batch)
    payload = [v for v in wb.args.values() if v is not None]
    jax.block_until_ready(jax.device_put(payload))
    t0 = time.perf_counter()
    for _ in range(N_TIMED):
        jax.block_until_ready(jax.device_put(payload))
    rate = N_TIMED * B / (time.perf_counter() - t0)
    return rate, wb.wire_bytes, wb.logical_bytes


# The --wire sweep's three variants (README "Wire format"): the
# bit-identical legacy layout, the packed CSR wire, and packed with
# f16 values/weights.
WIRE_VARIANTS = (("padded-wide", "padded", "wide"),
                 ("packed-wide", "packed", "wide"),
                 ("packed-narrow", "packed", "narrow"))


def run_wire_sweep(path):
    """The wire-format trio on the headline corpus shape: ``h2d_only``
    (device_put rate of the variant's actual payload) and ``e2e`` (the
    full loop through the variant's dispatch) for padded-wide vs
    packed-wide vs packed-narrow, plus bytes/example on the wire — the
    ISSUE 15 acceptance artifact (`python bench.py --wire` /
    `make bench-wire`; pinned in the full artifact's "wire" object)."""
    import dataclasses
    from fast_tffm_tpu.models.fm import ModelSpec, make_train_step
    out = {}
    for name, wf, wd in WIRE_VARIANTS:
        cfg = dataclasses.replace(make_cfg(path), wire_format=wf,
                                  wire_dtypes=wd)
        step = make_train_step(ModelSpec.from_config(cfg, training=True))
        h2d, wire_bytes, logical_bytes = run_h2d_only(cfg)
        e2e = statistics.median(
            run_e2e(cfg, step, n_warm=3) for _ in range(TRIALS))
        out[name] = {
            "h2d_only": round(h2d, 1),
            "e2e": round(e2e, 1),
            "bytes_per_example": round(wire_bytes / B, 1),
            "logical_bytes_per_example": round(logical_bytes / B, 1),
        }
    base = out["padded-wide"]["bytes_per_example"]
    for name in out:
        bpe = out[name]["bytes_per_example"]
        out[name]["bytes_savings_x"] = (round(base / bpe, 2)
                                        if bpe else None)
    return out


def wire_sweep_main():
    """Standalone wire-format sweep (`python bench.py --wire` /
    `make bench-wire`): one JSON line with the padded-wide vs
    packed-wide vs packed-narrow trio."""
    import tempfile
    _enable_compile_cache()
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "train.txt")
        lines = synth_lines((N_WARM + N_TIMED) * B, 1 << 20)
        with open(path, "w") as fh:
            fh.write("\n".join(lines) + "\n")
        del lines
        res = run_wire_sweep(path)
    packed = res["packed-wide"]
    print(json.dumps({
        "metric": "wire_bytes_savings_x",
        "value": packed["bytes_savings_x"],
        "unit": "padded bytes/example over packed (wide)",
        "wire": res,
    }))


def run_memory_profile(tmp):
    """The bytes-axis bench rows (README "Memory observability";
    `python bench.py --memory` / `make bench-memory`): bytes/row of
    the resident state, the planner-vs-ledger agreement and the
    peak-vs-model ratio measured off a REAL train run's mem/* gauges,
    and the serve reload spike (the old+new transient) off a real
    hot reload — the numbers the capacity frontiers (sharded / f16
    tables) will move."""
    from fast_tffm_tpu.checkpoint import CheckpointState
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.obs.attribution import summarize
    from fast_tffm_tpu.obs.memory import LEDGER, plan, table_bytes
    from fast_tffm_tpu.serve import ScorerServer
    from fast_tffm_tpu.train import train
    wd = os.path.join(tmp, "memory")
    os.makedirs(wd, exist_ok=True)
    path = os.path.join(wd, "train.txt")
    with open(path, "w") as fh:
        fh.write("\n".join(synth_lines(3072, 1 << 15)) + "\n")
    LEDGER.reset()
    # max_features 64 keeps the planner's wire ceiling honest for the
    # 39-feature synth lines (cap >= real nnz, same order as the
    # padded rectangle) — the agreement row measures planner-vs-ledger
    # drift, not ceiling slack from an uncapped default.
    cfg = FmConfig(vocabulary_size=1 << 15, factor_num=8,
                   batch_size=256, epoch_num=1, train_files=(path,),
                   max_features_per_example=64,
                   model_file=os.path.join(wd, "fm"),
                   metrics_file=os.path.join(wd, "metrics.jsonl"),
                   metrics_flush_steps=4)
    train(cfg)
    g = summarize([cfg.metrics_file]).get("gauges", {})
    model = table_bytes(cfg)
    # The ledger books one device's share of the session's mesh.
    p = plan(cfg, "train",
             shards=int(g.get("train/mesh_devices") or 1))
    # The stream's LAST mem/live_bytes is post-release (0); the
    # resident set the planner predicts is the mid-run maximum.
    live = 0.0
    with open(cfg.metrics_file) as fh:
        for line in fh:
            ev = json.loads(line)
            if ev.get("event") == "metrics":
                live = max(live,
                           ev.get("gauges", {}).get("mem/live_bytes",
                                                    0.0))
    peak = g.get("mem/peak_bytes") or 0.0
    out = {
        "model_bytes": model,
        "bytes_per_row": round(model / cfg.num_rows, 1),
        "ledger_live_bytes": int(live),
        "ledger_peak_bytes": int(peak),
        "plan_total_bytes": p["total_bytes"],
        # Planner prediction over the measured live ledger: the wire
        # row is a from-config ceiling, so slightly > 1.0 is expected;
        # far from 1.0 means planner and producers disagree.
        "plan_vs_ledger_x": (round(p["total_bytes"] / live, 3)
                             if live else None),
        # Peak over one dense model copy: table + optimizer state
        # (+ wire) — the "how much bigger than the .npz is the run"
        # multiplier capacity planning actually needs.
        "peak_vs_model_x": round(peak / model, 3) if model else None,
    }
    # Serve reload spike: a real server, a real hot reload — the gauge
    # carries the old+new transient the reload held until the swap.
    LEDGER.reset()
    swd = os.path.join(wd, "serve")
    os.makedirs(swd, exist_ok=True)
    scfg = FmConfig(vocabulary_size=1 << 15, factor_num=8,
                    max_features_per_example=48, bucket_ladder=(48,),
                    model_file=os.path.join(swd, "fm"),
                    serve_max_batch=64, serve_poll_seconds=60.0)
    rng = np.random.default_rng(0)
    table = rng.standard_normal(
        (scfg.ckpt_rows, scfg.row_dim)).astype(np.float32) * 0.01
    ckpt = CheckpointState(scfg.model_file)
    ckpt.save(1, table, np.full_like(table, 0.1),
              vocabulary_size=scfg.vocabulary_size, wait=True)
    ckpt.save(2, table, np.full_like(table, 0.1),
              vocabulary_size=scfg.vocabulary_size, wait=True)
    ckpt.publish_step(1)
    ckpt.close()
    del table
    server = ScorerServer(scfg, watch=False)
    try:
        if not server.reload_step(2):
            raise RuntimeError("bench --memory: hot reload of step 2 "
                               "failed")
        sg = server._reg.snapshot()["gauges"]
    finally:
        server.close()
    spike = sg.get("serve/reload_peak_bytes") or 0.0
    serve_model = table_bytes(scfg)
    out["serve_reload_spike_bytes"] = int(spike)
    out["serve_reload_spike_vs_model_x"] = (
        round(spike / serve_model, 3) if serve_model else None)
    LEDGER.reset()
    return out


def memory_main():
    """Standalone device-memory profile (`python bench.py --memory` /
    `make bench-memory`): one JSON line with the ledger/planner/reload
    rows."""
    import tempfile
    _enable_compile_cache()
    with tempfile.TemporaryDirectory() as tmp:
        res = run_memory_profile(tmp)
    print(json.dumps({
        "metric": "mem_peak_vs_model_x",
        "value": res["peak_vs_model_x"],
        "unit": "peak ledger bytes over one dense model copy",
        "memory": res,
    }))


def _enable_compile_cache():
    """Share the CLI's persistent XLA compile cache so the isolated
    line subprocesses (and repeat bench invocations) skip recompiles.
    Compile time is already excluded from every timed span by warmup;
    the cache only shrinks bench wall-clock."""
    from fast_tffm_tpu.compile_cache import (
        enable_compilation_cache)
    enable_compilation_cache()


def cfg_e2e_trials(cfg):
    """TRIALS end-to-end runs of a _line_cfg config through the shared
    timing protocol — the one body behind every cfg-generic e2e line
    (hashed, l64), so their protocols cannot drift apart."""
    from fast_tffm_tpu.models.fm import ModelSpec, make_train_step
    step = make_train_step(ModelSpec.from_config(cfg, training=True))
    return [run_e2e(cfg, step, n_warm=3) for _ in range(TRIALS)]


def run_hashed_e2e(cfg):
    """Hashed-id FM end-to-end trials: configs #2 (Criteo-1TB) and #5
    (1e9-feature iPinYou) both hash string ids, so the hashed parse +
    murmur path gets its own e2e line (the headline uses plain int ids).
    Reuses the headline data file — its int ids hash like any string.
    ``cfg`` comes from _line_cfg so the regime stamp and the measurement
    cannot diverge."""
    return cfg_e2e_trials(cfg)


def run_predict_e2e(cfg):
    """Batch-scoring throughput — the reference's second workload
    (SURVEY §3.4: file -> parse(keep_empty, line-aligned) -> score ->
    ordered scores): examples/sec over full sweeps of the headline file
    through the real predict path (the cross-file streaming scorer:
    fast_tffm_tpu.predict.predict_scores, chunked overlap fetches
    included). Sweep 0 pays the compiles and is discarded; then the
    same 1/2/4 ``host_threads`` regime search the train headline runs
    (keep_empty rides the parallel host plane since ISSUE 10) picks the
    best worker count, and TRIALS full sweeps run there. Returns
    (trial rates, best host_threads, search dict). ``cfg`` comes from
    _line_cfg (stamp/measurement unity)."""
    from fast_tffm_tpu.models.fm import init_table
    from fast_tffm_tpu.predict import predict_scores
    table = init_table(cfg, 0)

    def one_sweep(c):
        t0 = time.perf_counter()
        scores = predict_scores(c, table, c.train_files)
        return scores.shape[0] / (time.perf_counter() - t0)

    one_sweep(cfg)  # compile warmup, discarded
    search = {w: one_sweep(_with_workers(cfg, w))
              for w in HOST_WORKER_SWEEP}
    best = max(search, key=search.get)
    cfg = _with_workers(cfg, best)
    return [one_sweep(cfg) for _ in range(TRIALS)], best, search


def regime_stamp(cfg, training=True):
    """The (L, dedup, kernel) a config's hot loop actually runs —
    stamped into every bench line so a future reader of the JSON
    alone can tell WHICH cell of the kernel/bucket matrix
    (ops/kernel_choice.py) a number is (round-4 review: the bench's hand-tuned L=48 is exactly
    the cell where the Pallas/XLA winner flips, and the JSON didn't say
    so). Kernel goes through models.fm.resolved_kernel — the same
    resolution the traced step uses, so the stamp can't drift from the
    dispatch. ``training``: whether the line trains or scores (dedup =
    auto resolves by use on one chip)."""
    from fast_tffm_tpu.data.pipeline import effective_L_cap
    from fast_tffm_tpu.models.fm import ModelSpec, resolved_kernel
    spec = ModelSpec.from_config(cfg, training=training)
    if cfg.max_features_per_example == 0:
        # Unlimited features: the generic path extends buckets per
        # BATCH, so the widest width is data-dependent. auto's kernel
        # is only L-dependent under DEVICE dedup — for host dedup the
        # matrix resolves to xla at every width, so stamp that
        # deterministically rather than an uninformative null.
        kern = spec.kernel
        if kern == "auto":
            kern = None if spec.dedup == "device" else "xla"
        return {"L": None, "dedup": spec.dedup, "kernel": kern,
                "note": ("max_features_per_example=0: bucket width "
                         "is data-dependent"
                         + ("" if kern else "; so is auto's kernel "
                            "under device dedup"))}
    # The widest bucket a job can RUN is effective_L_cap, not the
    # ladder top: max_features_per_example past the ladder extends it
    # by DOUBLING rungs, and batches land per their own width — so
    # stamp every extended rung, not just the cap.
    rungs = [l for l in cfg.bucket_ladder]
    cap = effective_L_cap(cfg)
    while rungs[-1] < cap:
        rungs.append(rungs[-1] * 2)
    L = rungs[-1]
    stamp = {"L": L, "dedup": spec.dedup,
             "kernel": resolved_kernel(spec, L)}
    if len(rungs) > 1:
        # resolution is per bucket; with several rungs a single
        # (L, kernel) pair would claim a kernel most batches may not
        # run, so stamp every rung (bench configs today are all
        # single-rung — this keeps the stamp honest if that changes)
        stamp["kernel_per_bucket"] = {
            str(l): resolved_kernel(spec, l) for l in rungs}
    return stamp


def _line_cfg(name, train_path):
    """The config each named line measures — one factory for the line
    runners AND their regime stamps, so the stamp describes the config
    that actually ran."""
    import dataclasses
    tmp = os.path.dirname(train_path)
    if name == "ffm":
        return ffm_cfg(tmp)
    if name == "order3":
        return order3_cfg(tmp)
    if name == "hashed":
        return dataclasses.replace(make_cfg(train_path),
                                   hash_feature_id=True)
    if name == "predict":
        return make_cfg(train_path)
    if name == "k16":
        return dataclasses.replace(make_cfg(train_path), factor_num=16)
    if name == "l64":
        # The DEFAULT production regime for Criteo-39 data (auto ladder
        # lands at L=64). A one-chip train step takes the host unique,
        # where auto resolves to XLA at every width, so Pallas runs
        # only in run_k16's device-only pair.
        return dataclasses.replace(make_cfg(train_path),
                                   bucket_ladder=(64,))
    raise SystemExit(f"unknown bench line {name!r}")


def _run_line(name, train_path):
    """One secondary e2e line by name -> its result dict. The single
    dispatch both the subprocess entry and the in-process fallback go
    through, so they cannot drift apart."""
    tmp = os.path.dirname(train_path)
    cfg = _line_cfg(name, train_path)  # raises on unknown names
    out = {"regime": regime_stamp(cfg, training=name != "predict")}
    if name == "ffm":
        out["trials"] = run_ffm_e2e(tmp)
    elif name == "order3":
        out["trials"] = run_order3_e2e(tmp)
    elif name == "hashed":
        out["trials"] = run_hashed_e2e(cfg)
    elif name == "predict":
        trials, best, search = run_predict_e2e(cfg)
        out["trials"] = trials
        # The predict sweep's OWN data-plane regime (chosen by its
        # search — keep_empty batches are a different build shape from
        # the train headline's, so its best worker count is its own).
        out["host_threads"] = best
        out["host_threads_search"] = {str(w): round(v, 1)
                                      for w, v in search.items()}
    elif name == "l64":
        out["trials"] = cfg_e2e_trials(cfg)
    else:
        e2e, dev = run_k16(cfg)
        out.update(trials=e2e, device=dev)
    return out


def _line_main(name, train_path):
    """Subprocess entry for one isolated e2e line: prints one JSON
    object on stdout (see _isolated_line for why these run out of
    process)."""
    _enable_compile_cache()
    print(json.dumps(_run_line(name, train_path)))


# A line is ~1 min including compile (cache-cold); a child that takes
# 10x that is wedged (a stalling runtime is exactly the flakiness
# that motivated isolation) and the parent must not hang
# silently on it.
LINE_TIMEOUT_S = 600


def _isolated_line(name, train_path):
    """Run one e2e line in a fresh process and return its JSON dict,
    with ``isolation`` recording whether isolation actually happened.

    Measured on an earlier device (2026-07-30; unverified on the
    v5e — ROADMAP D8): an e2e line that
    sustains 0.9-1.2M examples/sec in a fresh process reads as low as
    118k when it runs AFTER other compiled programs in the same
    process — same-program repetition is stable (order3 x9: 830-926k),
    but mixing programs degrades every later line, and all TRIALS of a
    late line read low together, so medians alone cannot repair it.
    Local state is clean when it happens (no leaked threads,
    jax.live_arrays() empty), pointing at the remote device runtime;
    process isolation is the level that provably restores the rate.
    Failure handling never runs foreign programs before the headline:
    a subprocess that fails to spawn or crashes is marked ``isolation:
    "failed"`` and main() reruns it in-process only AFTER its own
    measurements (so the fallback's compiled programs cannot
    contaminate the headline; the rerun is then marked
    ``"in-process"`` — the caveat the number must carry). A child that
    WEDGES (timeout) is different again: the stall is the device
    runtime, so any rerun could hang the parent unbounded — that line
    stays null (``isolation: "timeout"``) and the rest of the artifact
    survives."""
    import subprocess
    import sys
    detail = ""
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--line", name,
             train_path],
            capture_output=True, text=True, timeout=LINE_TIMEOUT_S)
        if res.returncode == 0:
            try:
                out = json.loads(res.stdout.strip().splitlines()[-1])
            except (ValueError, IndexError):
                out = None
            if isinstance(out, dict):
                out["isolation"] = "subprocess"
                return out
            detail = f"unparseable stdout: {res.stdout[-200:]!r}"
        else:
            detail = (f"rc={res.returncode}, stderr tail: "
                      f"{res.stderr[-500:]}")
    except subprocess.TimeoutExpired:
        print(f"bench line {name}: subprocess wedged for "
              f"{LINE_TIMEOUT_S}s (stalled device runtime?); recording "
              f"null rather than risking a hung rerun", file=sys.stderr)
        return {"trials": None, "device": None, "isolation": "timeout"}
    print(f"bench line {name}: subprocess failed ({detail}); will rerun "
          f"in-process after the headline measurements", file=sys.stderr)
    return {"trials": None, "device": None, "isolation": "failed"}


# Serving-latency line shape: concurrent client threads x requests
# each, small variable-size requests (the online traffic shape — the
# admission queue's micro-batching is the thing under test).
SERVE_CLIENTS = 8
SERVE_REQUESTS_PER_CLIENT = 150


def run_serve_latency(tmp):
    """The serving path's bench line (README "Serving"): publish a
    checkpoint, run the REAL ScorerServer (verified load + warmed
    [B rung, L rung] ladder), fire concurrent variable-size requests
    through the in-process client, and report the request-latency
    p50/p99 the server's own histogram measured — the number the
    ``serve_p99_ms`` row pins and fmstat's SERVING section shows in
    production."""
    import threading
    from fast_tffm_tpu.checkpoint import CheckpointState
    from fast_tffm_tpu.config import FmConfig
    from fast_tffm_tpu.serve import ScoreClient, ScorerServer
    wd = os.path.join(tmp, "serve")
    os.makedirs(wd, exist_ok=True)
    cfg = FmConfig(vocabulary_size=1 << 20, factor_num=8,
                   max_features_per_example=48, bucket_ladder=(48,),
                   model_file=os.path.join(wd, "fm"),
                   serve_max_batch=256, serve_max_wait_ms=2.0,
                   serve_poll_seconds=60.0)
    rng = np.random.default_rng(0)
    table = rng.standard_normal(
        (cfg.ckpt_rows, cfg.row_dim)).astype(np.float32) * 0.01
    ckpt = CheckpointState(cfg.model_file)
    ckpt.save(1, table, np.full_like(table, 0.1),
              vocabulary_size=cfg.vocabulary_size, wait=True)
    ckpt.publish_step(1)
    ckpt.close()
    del table
    req_pool = synth_lines(512, 1 << 20, seed=7)
    server = ScorerServer(cfg, watch=False)
    client = ScoreClient(server)
    errors = []

    def fire(worker):
        r = np.random.default_rng(worker)
        try:
            for _ in range(SERVE_REQUESTS_PER_CLIENT):
                k = int(r.integers(1, 9))
                lo = int(r.integers(0, len(req_pool) - k))
                client.score(req_pool[lo:lo + k], timeout=120)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=fire, args=(i,))
               for i in range(SERVE_CLIENTS)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    stats = server.stats()
    server.close()
    if errors:
        raise errors[0]
    return {
        "p50_ms": round(stats["latency_p50_ms"], 2),
        "p99_ms": round(stats["latency_p99_ms"], 2),
        "requests": stats["requests"],
        "requests_per_sec": round(stats["requests"] / dt, 1),
        "examples_per_sec": round(stats["examples"] / dt, 1),
        "flushes": stats["flushes"],
        "clients": SERVE_CLIENTS,
    }


# Fleet-latency line shape (ISSUE 19): the serving soak's traffic
# through the REAL front door — FleetSupervisor children behind the
# failover proxy over loopback HTTP — with a fixed request count per
# client so req/s is a client-side measurement, comparable between the
# single-replica baseline and the fleet shape.
FLEET_REPLICAS = 3
FLEET_CLIENTS = 8
FLEET_REQUESTS_PER_CLIENT = 60


def run_fleet_latency(tmp):
    """The serving fleet's bench line (README "Serving fleet"): train
    and publish once, then run the SAME fixed concurrent-client load
    against two real front doors — ONE directly-served replica child
    (what ``run_tffm.py serve`` is) and the ``FleetSupervisor`` fleet
    behind the failover proxy. ``throughput_x`` is therefore the whole
    fleet claim: fan-out gain minus the proxy hop's cost, measured
    client-side over loopback HTTP (each replica is a real child
    process paying its own admission queue)."""
    import dataclasses as dc
    import http.client
    import threading
    from fast_tffm_tpu.checkpoint import CheckpointState, list_step_dirs
    from fast_tffm_tpu.config import load_config
    from fast_tffm_tpu.serve.fleet import FleetSupervisor, ReplicaProc
    from fast_tffm_tpu.train import train
    from tools.fmchaos import (_corpus_lines, _fleet_cfg_file,
                               _free_port_block, _write_corpus)
    from tools.fmckpt import cmd_publish

    wd = os.path.join(tmp, "fleet")
    os.makedirs(wd, exist_ok=True)
    data = os.path.join(wd, "train.txt")
    _write_corpus(data, 400, 0)
    # Train + publish ONCE; both front doors serve this step.
    cfg_path = _fleet_cfg_file(
        wd, data, replicas=FLEET_REPLICAS,
        base_port=_free_port_block(FLEET_REPLICAS + 1),
        serve_max_batch=64)
    cfg = load_config(cfg_path)
    train(dc.replace(cfg, metrics_file=""))
    ckpt = CheckpointState(cfg.model_file)
    step = list_step_dirs(ckpt.directory)[-1]
    ckpt.close()
    if cmd_publish(cfg.model_file + ".ckpt", step) != 0:
        raise RuntimeError(f"publish of step {step} failed")
    req_pool = _corpus_lines(60, seed=99)

    def soak(port, replicas):
        lat, failures = [], []
        lock = threading.Lock()

        def fire(worker):
            rng = np.random.default_rng(worker)
            try:
                for _ in range(FLEET_REQUESTS_PER_CLIENT):
                    k = int(rng.integers(1, 6))
                    lo = int(rng.integers(0, len(req_pool) - k))
                    body = ("\n".join(req_pool[lo:lo + k])
                            + "\n").encode("utf-8")
                    t0 = time.perf_counter()
                    conn = http.client.HTTPConnection(
                        "127.0.0.1", port, timeout=60)
                    try:
                        conn.request(
                            "POST", "/score", body=body,
                            headers={"Content-Type": "text/plain"})
                        resp = conn.getresponse()
                        resp.read()
                        if resp.status != 200:
                            raise RuntimeError(f"HTTP {resp.status}")
                    finally:
                        conn.close()
                    with lock:
                        lat.append((time.perf_counter() - t0) * 1e3)
            except Exception as e:  # noqa: BLE001 - surfaced below
                failures.append(repr(e))

        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(FLEET_CLIENTS)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        if failures:
            raise RuntimeError(
                f"{len(failures)} client failure(s): {failures[:3]}")
        return {
            "replicas": replicas,
            "p50_ms": round(float(np.percentile(lat, 50)), 2),
            "p99_ms": round(float(np.percentile(lat, 99)), 2),
            "requests": len(lat),
            "requests_per_sec": round(len(lat) / dt, 1),
        }

    # Baseline: one replica child served DIRECTLY on its own port —
    # this is `run_tffm.py serve` (no proxy hop in the path).
    solo = ReplicaProc(0, cfg, cfg_path)
    solo.spawn()
    try:
        deadline = time.monotonic() + 300
        while not solo.is_ready():
            if time.monotonic() > deadline:
                raise RuntimeError("baseline replica never became ready")
            time.sleep(0.1)
        single = soak(solo.port, 1)
    finally:
        solo.terminate()
        solo.reap()

    sup = FleetSupervisor(cfg, cfg_path).start()
    try:
        if not sup.wait_ready(FLEET_REPLICAS, timeout=300):
            raise RuntimeError(
                f"fleet never reached {FLEET_REPLICAS} ready replicas")
        fleet = soak(sup.proxy_port, FLEET_REPLICAS)
    finally:
        sup.stop()
    return {
        "single": single,
        "fleet": fleet,
        "clients": FLEET_CLIENTS,
        "requests_per_client": FLEET_REQUESTS_PER_CLIENT,
        "throughput_x": round(fleet["requests_per_sec"]
                              / single["requests_per_sec"], 2)
        if single["requests_per_sec"] else None,
    }


def run_quality_eval_cost(cfg):
    """The per-publish quality loop's cost line (README "SLOs & quality
    gate"): one full validation sweep through train.evaluate WITH the
    QualityStats collector vs without, on the headline corpus shape.
    The collector rides the sweep's own score fetches, so the ratio is
    the whole claim — near 1.0 means the gate's quality numbers are
    effectively free on top of a validation pass the publish settle was
    going to pay anyway. Returns (plain ex/s, collected ex/s, one
    collected-sweep seconds)."""
    from fast_tffm_tpu.models.fm import init_table
    from fast_tffm_tpu.obs.quality import QualityStats
    from fast_tffm_tpu.train import evaluate
    table = init_table(cfg, cfg.seed)
    # untimed warmup: compile the scorer once
    evaluate(cfg, table, cfg.train_files, max_batches=2)

    def sweep(with_stats):
        stats = QualityStats(cfg.loss_type) if with_stats else None
        t0 = time.perf_counter()
        _auc, n = evaluate(cfg, table, cfg.train_files, collect=stats)
        dt = time.perf_counter() - t0
        if with_stats:
            assert stats.loss is not None  # the collector really ran
        return n / dt, dt

    plain = statistics.median(sweep(False)[0] for _ in range(TRIALS))
    pairs = [sweep(True) for _ in range(TRIALS)]
    collected = statistics.median(r for r, _ in pairs)
    secs = statistics.median(dt for _, dt in pairs)
    return plain, collected, secs


def _make_bench_telemetry(cfg):
    """Optional run-telemetry stream (obs/) for the bench: set
    FM_METRICS_FILE to write the same JSONL schema production train/
    predict runs emit, with the bench's measured ceilings as
    ``bench/*`` gauges — so `python -m tools.fmstat` renders the same
    attribution table for a bench artifact and a real run, directly
    comparable. Off (None) without the env var: the bench's timed
    loops then run with zero instrumentation overhead."""
    path = os.environ.get("FM_METRICS_FILE")
    if not path:
        return None
    from fast_tffm_tpu.obs.telemetry import RunTelemetry, run_meta
    return RunTelemetry(path, meta=run_meta(cfg, "bench"),
                        flush_steps=0)


def main():
    import tempfile

    from fast_tffm_tpu.models.fm import ModelSpec, make_train_step

    _enable_compile_cache()
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "train.txt")
        lines = synth_lines((N_WARM + N_TIMED) * B, 1 << 20)
        with open(path, "w") as fh:
            fh.write("\n".join(lines) + "\n")
        del lines

        # The isolated lines run FIRST, before this process touches the
        # device: on runtimes with exclusive per-process TPU locking a
        # child could not initialize while the parent holds the chip
        # (a TPU belongs to one process at a time), and nothing below
        # needs to have run before them.
        ffm_res = _isolated_line("ffm", path)
        order3_res = _isolated_line("order3", path)
        hashed_res = _isolated_line("hashed", path)
        predict_res = _isolated_line("predict", path)
        k16_res = _isolated_line("k16", path)
        l64_res = _isolated_line("l64", path)

        cfg = make_cfg(path)
        spec = ModelSpec.from_config(cfg, training=True)
        step = make_train_step(spec)

        # e2e regime search over the parallel host data plane: one
        # quick trial per worker count picks the best host_threads;
        # the headline then runs its full TRIALS there, and the
        # host_only ceiling is measured at the same setting (the
        # ceiling must describe the loop the headline actually ran).
        search = {w: run_e2e(_with_workers(cfg, w), step, n_warm=3)
                  for w in HOST_WORKER_SWEEP}
        best_workers = max(search, key=search.get)
        cfg = _with_workers(cfg, best_workers)

        tel = _make_bench_telemetry(cfg)
        from fast_tffm_tpu.obs.telemetry import activate
        try:
            with activate(tel):
                # Headline trials run with the pipeline instrumentation
                # ACTIVE when FM_METRICS_FILE is set — the measured
                # number then includes (and bounds) the telemetry
                # overhead.
                e2e = [run_e2e(cfg, step) for _ in range(TRIALS)]
                host = run_host_only(cfg)
            # The 1/2/4-worker host_only sweep: the parallel plane's
            # scaling artifact (1 = the serial pre-parallel pipeline).
            # Every point runs OUTSIDE the activate() block — mixing
            # one instrumented measurement (the ceiling above pays the
            # telemetry overhead deliberately) into the sweep would
            # bias the scaling ratio against the instrumented point.
            host_workers = {
                str(w): run_host_only(_with_workers(cfg, w))
                for w in HOST_WORKER_SWEEP}
            dev = run_device_only(cfg, step)
            h2d, _, _ = run_h2d_only(cfg)
            # Per-worker input rate of the 2-way byte-range sharded
            # fast path (what each process's pipeline sustains in
            # multi-process mode).
            shard = run_host_only(cfg, shard_index=0, num_shards=2,
                                  raw_ids=False)
            if tel is not None:
                tel.set("bench/e2e", statistics.median(e2e))
                tel.set("bench/host_only", host)
                tel.set("bench/device_only", dev)
                tel.set("bench/h2d_only", h2d)
                tel.set("bench/sharded_input_per_worker", shard)
        finally:
            # The sink buffers EVERYTHING until close; without this a
            # mid-measurement crash leaves a zero-byte metrics file
            # (same lifecycle contract train()/predict() keep).
            if tel is not None:
                tel.close()

        # Deferred in-process fallbacks for failed (not wedged) line
        # subprocesses — AFTER the parent's own measurements, so a
        # fallback's compiled programs cannot contaminate the headline
        # (see _isolated_line).
        for name, res in (("ffm", ffm_res), ("order3", order3_res),
                          ("hashed", hashed_res), ("predict", predict_res),
                          ("k16", k16_res), ("l64", l64_res)):
            if res["isolation"] == "failed":
                # A reproducible crash (not a spawn flake) raises here
                # too — record the null line rather than aborting main()
                # and losing the measurements already taken.
                try:
                    res.update(_run_line(name, path))
                    res["isolation"] = "in-process"
                except Exception as e:  # noqa: BLE001 - artifact survival
                    import sys
                    print(f"bench line {name}: in-process fallback also "
                          f"failed ({type(e).__name__}: {e}); recording "
                          f"null", file=sys.stderr)
        ffm, order3 = ffm_res["trials"], order3_res["trials"]
        hashed, pred = hashed_res["trials"], predict_res["trials"]
        k16, k16_dev = k16_res["trials"], k16_res["device"]
        l64 = l64_res["trials"]

        # Serving-path soak (ISSUE 11): the online scorer's request
        # latency under concurrent clients — a LATENCY line beside the
        # throughput lines above (`python bench.py --serve` standalone).
        try:
            serve_res = run_serve_latency(tmp)
        except Exception as e:  # noqa: BLE001 - artifact survival
            import sys
            print(f"bench serve line failed ({type(e).__name__}: {e}); "
                  f"recording null", file=sys.stderr)
            serve_res = None

        # Quality-loop eval cost (ISSUE 13): the publish gate's
        # validation sweep with vs without the QualityStats collector.
        try:
            quality_res = run_quality_eval_cost(cfg)
        except Exception as e:  # noqa: BLE001 - artifact survival
            import sys
            print(f"bench quality line failed ({type(e).__name__}: "
                  f"{e}); recording null", file=sys.stderr)
            quality_res = None

        # Wire-format trio (ISSUE 15): padded-wide vs packed-wide vs
        # packed-narrow on h2d_only and e2e — the ROADMAP item 2
        # bytes-per-example lever, pinned beside the ceilings it moves.
        try:
            wire_res = run_wire_sweep(path)
        except Exception as e:  # noqa: BLE001 - artifact survival
            import sys
            print(f"bench wire sweep failed ({type(e).__name__}: {e}); "
                  f"recording null", file=sys.stderr)
            wire_res = None

    def med(trials):  # None survives a timed-out line (see _isolated_line)
        return round(statistics.median(trials), 1) if trials else None

    eps = statistics.median(e2e)
    print(json.dumps({
        "metric": "train_examples_per_sec_per_chip",
        "value": round(eps, 1),
        "unit": "examples/sec",
        "vs_baseline": round(eps / NORTH_STAR_PER_CHIP, 3),
        # Which cell of the kernel/bucket matrix the headline
        # measured (see regime_stamp) — and the same per secondary line
        # below, so the JSON is self-describing about its regimes.
        "regime": regime_stamp(cfg),
        "line_regimes": {"ffm": ffm_res.get("regime"),
                         "order3": order3_res.get("regime"),
                         "hashed": hashed_res.get("regime"),
                         "predict": predict_res.get("regime"),
                         "k16": k16_res.get("regime"),
                         "l64": l64_res.get("regime")},
        "e2e_trials": [round(v, 1) for v in e2e],
        # The pipeline's ACTUAL build parallelism (data-plane workers,
        # chosen by the e2e regime search) vs the C++ builder's native
        # feed parse threads — two different axes; r05 conflated them.
        "host_threads": best_workers,
        "host_threads_search": {str(w): round(v, 1)
                                for w, v in search.items()},
        "parse_threads": _parse_threads(),
        "host_only": round(host, 1),
        "host_only_workers": {w: round(v, 1)
                              for w, v in host_workers.items()},
        "device_only": round(dev, 1),
        "h2d_only": round(h2d, 1),
        "sharded_input_per_worker": round(shard, 1),
        "ffm_e2e": med(ffm),
        "ffm_e2e_trials": [round(v, 1) for v in ffm] if ffm else None,
        "order3_e2e": med(order3),
        "order3_e2e_trials":
            [round(v, 1) for v in order3] if order3 else None,
        "hashed_e2e": med(hashed),
        "hashed_e2e_trials":
            [round(v, 1) for v in hashed] if hashed else None,
        "predict_e2e": med(pred),
        "predict_e2e_trials":
            [round(v, 1) for v in pred] if pred else None,
        # The predict gap, PINNED (ISSUE 10 acceptance): predict sweep
        # rate over the train headline on the same chip. An earlier
        # device read 0.068 with the per-file teardown pipeline
        # (record removed in PR 21); the streaming scorer must keep
        # this from silently regressing toward it.
        "predict_vs_train_ratio":
            round(med(pred) / eps, 4) if pred and eps else None,
        # The predict sweep's own data-plane regime search (keep_empty
        # on the parallel host plane).
        "predict_host_threads": predict_res.get("host_threads"),
        "predict_host_threads_search":
            predict_res.get("host_threads_search"),
        # The serving path's latency SLO numbers (README "Serving"):
        # request-latency quantiles over SERVE_CLIENTS concurrent
        # clients through the real admission queue + warmed ladder.
        "serve_p50_ms": serve_res["p50_ms"] if serve_res else None,
        "serve_p99_ms": serve_res["p99_ms"] if serve_res else None,
        "serve_requests_per_sec":
            serve_res["requests_per_sec"] if serve_res else None,
        "serve_examples_per_sec":
            serve_res["examples_per_sec"] if serve_res else None,
        # The per-publish quality loop's cost (README "SLOs & quality
        # gate"): eval sweep rate with the QualityStats collector
        # riding the fetches vs the plain validation sweep, and the
        # one-sweep wall the publish settle pays. Ratio ~1.0 = the
        # gate's quality numbers are free on top of validation.
        "quality_eval_examples_per_sec":
            round(quality_res[1], 1) if quality_res else None,
        "quality_eval_plain_examples_per_sec":
            round(quality_res[0], 1) if quality_res else None,
        "quality_vs_plain_eval_ratio":
            round(quality_res[1] / quality_res[0], 4)
            if quality_res and quality_res[0] else None,
        "quality_eval_sweep_seconds":
            round(quality_res[2], 3) if quality_res else None,
        # The wire-format trio (README "Wire format"): per-variant
        # h2d_only / e2e / bytes-per-example, with the packed savings
        # multiple over the padded layout.
        "wire": wire_res,
        "k16_e2e": med(k16),
        "k16_e2e_trials": [round(v, 1) for v in k16] if k16 else None,
        "l64_e2e": med(l64),
        "l64_e2e_trials": [round(v, 1) for v in l64] if l64 else None,
        "k16_device_pallas": round(k16_dev["pallas"], 1) if k16_dev
        else None,
        "k16_device_xla": round(k16_dev["xla"], 1) if k16_dev else None,
        # Whether each isolated line actually ran in a fresh process
        # (see _isolated_line on the measured in-process cross-program
        # degradation); "in-process" marks a fallback whose number
        # carries that caveat.
        "line_isolation": {"ffm": ffm_res["isolation"],
                           "order3": order3_res["isolation"],
                           "hashed": hashed_res["isolation"],
                           "predict": predict_res["isolation"],
                           "k16": k16_res["isolation"],
                           "l64": l64_res["isolation"]},
    }))


def host_sweep_main():
    """Standalone host-only worker sweep (`make bench-host` /
    `python bench.py --host-sweep`): the parallel data plane's
    1/2/4-worker batch-build rates on the headline corpus shape, no
    device required (raw_ids=False keeps the measurement on the
    host-dedup build — the one multi-process mode must sustain — and
    off any jitted-spec resolution). One JSON line, same spirit as the
    main artifact: the 4v1 ratio is the scaling claim, attributable."""
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "train.txt")
        lines = synth_lines((N_WARM + N_TIMED) * B, 1 << 20)
        with open(path, "w") as fh:
            fh.write("\n".join(lines) + "\n")
        del lines
        cfg = make_cfg(path)
        rates = {str(w): round(run_host_only(_with_workers(cfg, w),
                                             raw_ids=False), 1)
                 for w in HOST_WORKER_SWEEP}
    print(json.dumps({
        "metric": "host_only_examples_per_sec",
        "unit": "examples/sec",
        "host_only_workers": rates,
        "scaling_4v1": round(rates["4"] / rates["1"], 3)
        if rates.get("1") else None,
        "parse_threads": _parse_threads(),
    }))


def serve_latency_main():
    """Standalone serving-latency line (`python bench.py --serve`):
    the run_serve_latency soak without the ~7 other lines the full
    bench pays for. One JSON line."""
    import tempfile
    _enable_compile_cache()
    with tempfile.TemporaryDirectory() as tmp:
        res = run_serve_latency(tmp)
    print(json.dumps({
        "metric": "serve_request_latency_ms",
        "value": res["p99_ms"],
        "unit": "ms (p99)",
        **res,
    }))


def fleet_main():
    """Standalone serving-fleet line (`python bench.py --fleet` /
    `make bench-fleet`): run_fleet_latency without the rest of the
    bench — the fleet's client-side p99 as the headline, with the
    single-replica-behind-the-proxy baseline and the req/s scaling
    factor beside it. One JSON line."""
    import tempfile
    _enable_compile_cache()
    with tempfile.TemporaryDirectory() as tmp:
        res = run_fleet_latency(tmp)
    print(json.dumps({
        "metric": "fleet_request_latency_ms",
        "value": res["fleet"]["p99_ms"],
        "unit": f"ms (p99, {FLEET_REPLICAS} replicas behind the proxy)",
        **res,
    }))


def vocab_overhead_main():
    """Standalone admission-path overhead line (`python bench.py
    --vocab` / `make bench-vocab`): train e2e examples/sec at
    ``vocab_mode = admit`` vs ``fixed`` on the same hashed-id corpus —
    the admit run pays the per-batch remap (binary-search over the
    frozen slot map + host re-dedup) and the per-step sketch
    observation, against a map POPULATED by a real warmup pass + one
    barrier (the steady state between barriers, which is what a long
    stream runs in). Target: ratio >= 0.95 (<= 5% regression). One
    JSON line."""
    import dataclasses
    import tempfile
    from fast_tffm_tpu.models.fm import ModelSpec, make_train_step
    from fast_tffm_tpu.vocab.table import VocabRuntime
    _enable_compile_cache()
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "train.txt")
        lines = synth_lines((N_WARM + N_TIMED) * B, 1 << 20)
        with open(path, "w") as fh:
            fh.write("\n".join(lines) + "\n")
        del lines
        base = dataclasses.replace(make_cfg(path), hash_feature_id=True,
                                   vocabulary_size=1 << 17)
        admit_cfg = dataclasses.replace(
            base, vocab_mode="admit", vocab_admit_threshold=2.0,
            vocab_decay=0.5, vocab_sketch_mb=1.0)
        fixed_step = make_train_step(
            ModelSpec.from_config(base, training=True))
        fixed = [run_e2e(base, fixed_step) for _ in range(TRIALS)]
        vocab = VocabRuntime.from_config(admit_cfg)
        # Populate the slot map the way a running stream would: one
        # untimed observation pass + a barrier, so the timed trials
        # remap through a realistic frozen map instead of an empty one
        # (all-cold lookups would understate the binary-search cost).
        from fast_tffm_tpu.data.pipeline import batch_iterator
        for batch in batch_iterator(admit_cfg, admit_cfg.train_files,
                                    training=True,
                                    raw_ids=_raw_mode(admit_cfg),
                                    vocab=vocab):
            vocab.note_trained(batch)
        vocab.barrier(None)
        admit_step = make_train_step(
            ModelSpec.from_config(admit_cfg, training=True))
        admit = [run_e2e(admit_cfg, admit_step, vocab=vocab)
                 for _ in range(TRIALS)]
    f_med = statistics.median(fixed)
    a_med = statistics.median(admit)
    print(json.dumps({
        "metric": "vocab_admit_vs_fixed_ratio",
        "value": round(a_med / f_med, 3) if f_med else None,
        "unit": "admit/fixed train examples/sec (target >= 0.95)",
        "vocab_fixed_eps": round(f_med, 1),
        "vocab_admit_eps": round(a_med, 1),
        "vocab_fixed_trials": [round(v, 1) for v in fixed],
        "vocab_admit_trials": [round(v, 1) for v in admit],
        "vocab_live_rows": vocab.live_rows,
    }))


def predict_sweep_main():
    """Standalone predict line (`make bench-predict` / `python bench.py
    --predict`): TRIALS full sweeps of the cross-file streaming scorer
    on the headline corpus shape, plus its 1/2/4 ``host_threads``
    regime search — one JSON line, without the ~6 other lines the full
    bench pays for. The pinned ``predict_vs_train_ratio`` lives in the
    full artifact (`python bench.py`), where the train headline it
    divides by is measured in the same run."""
    import tempfile
    _enable_compile_cache()
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "train.txt")
        lines = synth_lines((N_WARM + N_TIMED) * B, 1 << 20)
        with open(path, "w") as fh:
            fh.write("\n".join(lines) + "\n")
        del lines
        res = _run_line("predict", path)
    trials = res["trials"]
    print(json.dumps({
        "metric": "predict_examples_per_sec_per_chip",
        "value": round(statistics.median(trials), 1),
        "unit": "examples/sec",
        "predict_e2e_trials": [round(v, 1) for v in trials],
        "host_threads": res["host_threads"],
        "host_threads_search": res["host_threads_search"],
        "regime": res["regime"],
    }))


def multihost_main():
    """Standalone multi-host scaling-efficiency line (`python bench.py
    --multihost` / `make bench-multihost`): REAL 1- and 2-process
    localhost clusters (jax.distributed + gloo, the same transport the
    lockstep protocol runs in production CPU smoke clusters) train the
    same line-sharded corpus; the tracked number is per-worker
    efficiency — (2-worker global rate / 2) / 1-worker rate — measured
    from the metrics stream's loop time + example counters, so cluster
    bring-up (tens of seconds of interpreter+join) stays OUT of the
    scaling claim. This is ROADMAP item 4's membership-change number:
    elastic shrink/grow land on exactly this lockstep plane, so a
    regression in the overlap/window protocol moves this row."""
    import subprocess
    import sys
    import tempfile
    import socket as socketlib

    def free_port() -> int:
        with socketlib.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    from fast_tffm_tpu.obs.attribution import (efficiency_table,
                                               summarize)

    def loop_rate(paths) -> float:
        """Examples per WORKER-second: summarize() sums both the
        example counters and the per-shard loop (step_seconds) sums
        across the workers' metrics files, so global examples over
        summed loop seconds is already the per-worker rate — for W=1
        it is simply the single-process rate, so the efficiency below
        is a direct ratio (no extra division by W: that would halve
        the metric, reporting perfect scaling as 0.5)."""
        s = summarize(paths)
        loop = (s["hists"].get("train/step_seconds") or {}).get("sum")
        examples = s["counters"].get("train/examples")
        return (examples / loop) if loop and examples else 0.0

    n_lines, epochs = 9728, 2  # 304 even steps/epoch at B=32
    repo = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    with tempfile.TemporaryDirectory() as tmp:
        data = os.path.join(tmp, "train.txt")
        lines = synth_lines(n_lines, 1 << 17)
        with open(data, "w") as fh:
            fh.write("\n".join(lines) + "\n")
        del lines
        results = {}
        for w in (1, 2):
            wdir = os.path.join(tmp, f"w{w}")
            os.makedirs(wdir)
            metrics = os.path.join(wdir, "metrics.jsonl")
            coord = free_port()
            hosts = ",".join(f"localhost:{coord - 1000 + i}"
                             for i in range(w))
            cfg_path = os.path.join(wdir, "bench.cfg")
            with open(cfg_path, "w") as fh:
                fh.write(f"""
[General]
vocabulary_size = {1 << 17}
factor_num = 8
hash_feature_id = True
model_file = {os.path.join(wdir, 'model', 'fm')}

[Train]
train_files = {data}
epoch_num = {epochs}
batch_size = 32
learning_rate = 0.05
shuffle = False
log_steps = 0
metrics_file = {metrics}
trace_spans = True
max_features_per_example = 64

[Cluster]
worker_hosts = {hosts}
""")
            argv = [sys.executable, "run_tffm.py", "train", cfg_path]
            procs = []
            for i in range(w):
                a = argv + (["dist_train", "worker", str(i)]
                            if w > 1 else [])
                procs.append(subprocess.Popen(
                    a, cwd=repo, env=env,
                    stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL))
            rcs = [p.wait(timeout=900) for p in procs]
            if any(rcs):
                raise SystemExit(f"multihost bench: {w}-worker run "
                                 f"failed (rcs {rcs})")
            shards = [metrics] + [f"{metrics}.p{i}"
                                  for i in range(1, w)
                                  if os.path.exists(f"{metrics}.p{i}")]
            results[w] = loop_rate(shards)
            if w == 2:
                # Attach the step-anatomy phase breakdown so the
                # efficiency row carries its own WHY: the anatomy/*
                # gauges the workers pre-aggregate at barrier flushes
                # say where the lost fraction went (fmstat EFFICIENCY
                # and fmtrace --anatomy read the same surface).
                eff = efficiency_table(summarize(shards))
                from fast_tffm_tpu.obs import anatomy as anat_mod
                # The 1-worker leg's rate is the baseline that turns
                # the trace replay's coordination efficiency into the
                # ABSOLUTE per-worker number (it prices the stall
                # inside the dispatched program, which host spans
                # cannot see) — directly comparable to this row's
                # counter-derived "value".
                rep = anat_mod.report(shards,
                                      baseline_eps=results.get(1))
                anatomy = {
                    "verdict": rep.get("verdict"),
                    "efficiency": (round(rep["efficiency"], 3)
                                   if "efficiency" in rep else None),
                    "efficiency_vs_single": (
                        round(rep["efficiency_vs_single"], 3)
                        if rep.get("efficiency_vs_single") is not None
                        else None),
                    "straggler_rank": rep.get("straggler_rank"),
                    "per_worker": {
                        f"p{p}": {
                            "efficiency": round(r["efficiency"], 3),
                            "phase_fractions": {
                                k: round(v / r["wall_seconds"], 3)
                                for k, v in r["phases"].items()
                                if v},
                        } for p, r in (eff["ranks"].items()
                                       if eff else ())},
                } if (eff or "efficiency" in rep) else None
    r1, r2 = results.get(1, 0.0), results.get(2, 0.0)
    print(json.dumps({
        "metric": "multihost_scaling_efficiency",
        "value": round(r2 / r1, 3) if r1 and r2 else None,
        "unit": "2-worker per-worker rate / 1-worker rate",
        "single_process_eps": round(r1, 1),
        "two_worker_per_worker_eps": round(r2, 1),
        "examples": n_lines * epochs,
        "anatomy": anatomy,
    }))


# Bench-row names matching one of these fragments are lower-is-better
# (latencies, per-example costs); everything else is a rate or a count
# where bigger is fine. --compare's direction heuristic.
_LOWER_BETTER = ("_ms", "_seconds", "seconds_per", "bytes_per",
                 "latency", "_wait", "p50", "p90", "p99")


def _numeric_leaves(obj, prefix=""):
    """Flatten a bench JSON artifact to {dotted.path: float} rows —
    the nested shape (host_threads_search, e2e_trials, ...) varies by
    line, so --compare diffs whatever numeric leaves both sides
    share rather than hard-coding a schema."""
    rows = {}
    if isinstance(obj, dict):
        for k, v in sorted(obj.items()):
            rows.update(_numeric_leaves(v, f"{prefix}{k}."))
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            rows.update(_numeric_leaves(v, f"{prefix}{i}."))
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        rows[prefix[:-1]] = float(obj)
    return rows


def _bench_rows(path):
    """Rows from a bench artifact: a raw bench line (the JSON one
    bench.py mode prints), a driver wrapper around one (diffs its
    "parsed" payload; the cmd/rc/tail envelope is not a metric), or a
    JSONL file of several such documents merged."""
    with open(path) as fh:
        text = fh.read()
    try:
        docs = [json.loads(text)]
    except ValueError:
        docs = [json.loads(ln) for ln in text.splitlines()
                if ln.strip()]
    rows = {}
    for doc in docs:
        if isinstance(doc, dict) and isinstance(doc.get("parsed"),
                                                dict):
            doc = doc["parsed"]
        rows.update(_numeric_leaves(doc))
    return rows


def compare_main():
    """Regression diff (`python bench.py --compare OLD.json NEW.json`
    / `make bench-diff`): per-row NEW/OLD ratios with a direction
    heuristic (_LOWER_BETTER) and a tolerance band; exits 1 when any
    shared row regressed past tolerance, so CI can gate on a saved
    baseline artifact without bespoke parsing."""
    import argparse
    import sys
    ap = argparse.ArgumentParser(
        prog="bench.py --compare",
        description="diff two bench JSON artifacts; exit 1 on "
                    "regression past --tolerance")
    ap.add_argument("old", help="baseline artifact (JSON or JSONL)")
    ap.add_argument("new", help="candidate artifact (JSON or JSONL)")
    ap.add_argument("--tolerance", type=float, default=0.85,
                    help="allowed NEW/OLD degradation ratio "
                         "(default 0.85: a rate may drop to 85%% of "
                         "baseline, a latency may grow to 1/0.85x)")
    args = ap.parse_args(sys.argv[2:])
    old, new = _bench_rows(args.old), _bench_rows(args.new)
    shared = sorted(set(old) & set(new))
    if not shared:
        raise SystemExit("bench --compare: no shared numeric rows "
                         f"between {args.old} and {args.new}")
    regressions = []
    print(f"{'row':<48} {'old':>12} {'new':>12} {'ratio':>8}  "
          f"dir  status")
    for k in shared:
        o, n = old[k], new[k]
        if o == 0:
            continue  # ratio undefined; zero baselines carry no bar
        ratio = n / o
        lower = any(f in k for f in _LOWER_BETTER)
        ok = (ratio <= 1.0 / args.tolerance) if lower \
            else (ratio >= args.tolerance)
        status = "ok" if ok else "REGRESSION"
        if not ok:
            regressions.append(k)
        print(f"{k:<48} {o:>12.4g} {n:>12.4g} {ratio:>8.3f}  "
              f"{'lo' if lower else 'hi'}   {status}")
    for label, only in (("old", set(old) - set(new)),
                        ("new", set(new) - set(old))):
        for k in sorted(only):
            print(f"{k:<48} only in {label}")
    if regressions:
        print(f"{len(regressions)} regression(s) past tolerance "
              f"{args.tolerance}: {', '.join(regressions)}")
        raise SystemExit(1)
    print(f"no regressions across {len(shared)} shared row(s) at "
          f"tolerance {args.tolerance}")


if __name__ == "__main__":
    import sys
    if len(sys.argv) > 1 and sys.argv[1] == "--line":
        if len(sys.argv) != 4:
            raise SystemExit("usage: bench.py --line <name> <train_path>")
        _line_main(sys.argv[2], sys.argv[3])
    elif len(sys.argv) > 1 and sys.argv[1] == "--host-sweep":
        host_sweep_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--predict":
        predict_sweep_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--vocab":
        vocab_overhead_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--serve":
        serve_latency_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--fleet":
        fleet_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--multihost":
        multihost_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--compare":
        compare_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--wire":
        wire_sweep_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--memory":
        memory_main()
    else:
        main()
