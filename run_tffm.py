#!/usr/bin/env python
"""CLI-compatible entrypoint — the reference's ``run_tffm.py`` surface
(SURVEY.md §1 L1, §3):

    python run_tffm.py train   <cfg>
    python run_tffm.py train   <cfg> dist_train <job_name> <task_index>
    python run_tffm.py train   <cfg> --join
    python run_tffm.py predict <cfg>
    python run_tffm.py predict <cfg> dist_train <job_name> <task_index>
    python run_tffm.py serve   <cfg> [--replicas N]

``dist_train`` roles map onto synchronous jax.distributed processes
instead of TF1 ps/worker async-SGD (SURVEY §7): ``worker i`` becomes DP
process i; a ``ps`` role is accepted and exits with an explanatory
message, since parameter serving is subsumed by the row-sharded table.
``predict ... dist_train`` (an extension: the reference predicts
single-process) shards the predict input across the same worker
cluster and merges ordered score files on the chief.

``serve`` (an extension; README "Serving") runs the long-lived online
scorer: it loads the ``published`` checkpoint step, micro-batches
concurrent requests behind a stdlib HTTP front end (POST /score, GET
/healthz on ``serve_port``), and hot-reloads when the pointer moves.
SIGTERM/SIGINT drain and exit cleanly. ``--replicas N`` (or
``serve_replicas``; README "Serving fleet") instead runs the replica
supervisor: N scorer children on ``serve_port + i`` behind the
failover proxy on ``serve_proxy_port``, with health-gated routing,
capped-backoff restarts, staggered hot reloads, and canary scoring.

``train --join`` (an extension; README "Elastic multi-host") launches
a REPLACEMENT worker for a running ``elastic = grow`` cluster: it
publishes a join-request lease in ``<model_file>.hb/``, waits for the
cluster to admit it at a safe barrier, and comes up as an ordinary
member — verified checkpoint restore, re-balanced input shards and
all. Its worker slot is assigned by the cluster, so no task index is
given.
"""

from __future__ import annotations

import os
import sys

from fast_tffm_tpu.config import apply_env_overrides, load_config
from fast_tffm_tpu.compile_cache import enable_compilation_cache
from fast_tffm_tpu.utils.logging import get_logger


def _usage() -> int:
    print(__doc__, file=sys.stderr)
    return 2


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if len(argv) < 2 or argv[0] not in ("train", "predict", "serve"):
        return _usage()
    mode, cfg_path = argv[0], argv[1]
    rest = argv[2:]
    cfg = load_config(cfg_path)
    # One-off per-process overrides without editing the config file:
    # FM_METRICS_FILE (the `metrics_file` knob's values; "auto" =
    # <model_file>.metrics.jsonl — summarize with `python -m
    # tools.fmstat <file>`), FM_TRACE_SPANS / FM_WATCHDOG_STALL_SECONDS
    # for the timeline/health layer, and the serve-fleet knobs the
    # supervisor hands each replica (config.apply_env_overrides).
    cfg = apply_env_overrides(cfg)
    # Config only, no jax client: the fleet supervisor below never
    # opens the chip its replicas need.
    enable_compilation_cache(get_logger(log_file=cfg.log_file or None))

    if mode == "serve":
        replicas = None
        if rest and rest[0] == "--replicas":
            if len(rest) != 2:
                return _usage()
            try:
                replicas = int(rest[1])
            except ValueError:
                print(f"--replicas wants an integer, got {rest[1]!r}",
                      file=sys.stderr)
                return _usage()
            if replicas < 1:
                print("--replicas must be >= 1", file=sys.stderr)
                return _usage()
            rest = []
        if rest:
            print("serve takes no dist_train role: the scorer is "
                  "single-process; a multi-replica fleet is "
                  "`serve <cfg> --replicas N` (README 'Serving "
                  "fleet')", file=sys.stderr)
            return _usage()
        n = replicas if replicas is not None else cfg.serve_replicas
        if n > 1:
            from fast_tffm_tpu.serve.fleet import run_fleet
            return run_fleet(cfg, cfg_path, replicas=n)
        from fast_tffm_tpu.serve.frontend import run_serve
        return run_serve(cfg)

    job_name = task_index = None
    join = False
    if rest == ["--join"]:
        if mode != "train":
            print("--join is a train mode: a replacement worker joins "
                  "a running elastic = grow training cluster",
                  file=sys.stderr)
            return _usage()
        join = True
        rest = []
    if rest:
        if len(rest) != 3 or rest[0] != "dist_train":
            return _usage()
        job_name = rest[1]
        try:
            task_index = int(rest[2])
        except ValueError:
            # Same treatment as every other malformed argv form: the
            # usage text, not a raw int() traceback.
            print(f"dist_train task index must be an integer, got "
                  f"{rest[2]!r}", file=sys.stderr)
            return _usage()
        if job_name == "ps":
            print("fast_tffm_tpu has no parameter servers: the table is "
                  "row-sharded across the device mesh. Launch worker "
                  "roles only.", file=sys.stderr)
            return 0
        if job_name != "worker":
            return _usage()

    if mode == "predict":
        from fast_tffm_tpu.predict import predict
        predict(cfg, job_name=job_name, task_index=task_index)
        return 0

    from fast_tffm_tpu.train import train
    train(cfg, job_name, task_index, join=join)
    return 0


def _exit(rc: int) -> "None":
    """sys.exit, EXCEPT after a run that retired a dead cluster's
    jax.distributed client (elastic recovery / WorkerLostError fail
    fast): normal interpreter teardown destroys the retired
    coordination service, whose call cancellation trips the retired
    client's fatal error handler — a SIGABRT after an otherwise clean
    exit. Every durable artifact (checkpoint, metrics stream, logs,
    exports) is already closed by the drivers' finally blocks, so
    skipping C++ teardown of dead cluster plumbing via os._exit is the
    correct last step."""
    try:
        from fast_tffm_tpu.parallel.distributed import has_retired_clients
        retired = has_retired_clients()
    except Exception:
        retired = False
    if retired:
        try:
            # A RETIRED client's teardown is skipped (dead cluster,
            # doomed handshake) — but an elastic GROW may have formed
            # a LIVE cluster since (incumbents retire the old client,
            # then rejoin with the newcomers). That healthy client's
            # coordination service must be shut down with the proper
            # handshake, or os._exit below would tear it out from
            # under the peers mid-teardown — their error poll then
            # LOG(FATAL)-aborts an otherwise clean exit on THEIR side.
            import jax
            if jax.process_count() > 1:
                jax.distributed.shutdown()
        except Exception:
            pass  # a half-formed live client must not block the exit
        import logging
        logging.shutdown()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(rc)
    sys.exit(rc)


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:  # preserve explicit exit codes
        _exit(e.code if isinstance(e.code, int) else (0 if e.code is
                                                      None else 1))
    except KeyboardInterrupt:
        raise  # standard ^C semantics (exit 130), not a failure exit
    except Exception:
        import traceback
        traceback.print_exc()
        _exit(1)
    _exit(rc)
