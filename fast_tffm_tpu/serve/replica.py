"""Fleet replica child entry: ``python -m fast_tffm_tpu.serve.replica``.

One supervised ScorerServer process (README "Serving fleet"): loads
the config file the supervisor passes, applies the per-replica
``FM_<KNOB>`` env overrides the supervisor set (its own
``serve_port``, its metrics shard, ``serve_reload_mode = external``,
and ``serve_pointer = canary`` on the canary replica), and runs the
standard single-process serve driver — the same drain-on-SIGTERM
lifecycle ``run_tffm.py serve`` has, which is exactly what the
supervisor's terminate/reap sequence relies on.
"""

from __future__ import annotations

import sys

from fast_tffm_tpu.config import apply_env_overrides, load_config
from fast_tffm_tpu.compile_cache import enable_compilation_cache
from fast_tffm_tpu.utils.logging import get_logger


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if len(argv) != 1:
        print("usage: python -m fast_tffm_tpu.serve.replica <cfg>",
              file=sys.stderr)
        return 2
    cfg = apply_env_overrides(load_config(argv[0]))
    # A RESTARTED replica re-warms its shape ladder from the cache in
    # seconds instead of recompiling the matrix — the difference
    # between a restart gap and a restart outage.
    enable_compilation_cache(get_logger(log_file=cfg.log_file or None))
    from fast_tffm_tpu.serve.frontend import run_serve
    # Background warm-up: the fleet supervisor restarts on alive and
    # the proxy routes on ready, so a replica must answer /healthz
    # (ready: false) from the first second of its life.
    return run_serve(cfg, warmup="background")


if __name__ == "__main__":
    sys.exit(main())
