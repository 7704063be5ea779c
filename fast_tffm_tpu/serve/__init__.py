"""Online serving subsystem (README "Serving"): a long-lived scorer
process over the published checkpoint pointer.

The train->publish->serve loop's last leg: PR 8's stream driver saves,
manifest-verifies, and atomically repoints ``published`` in
``<model_file>.ckpt/``; this package watches that pointer, serves
scores for libsvm-formatted request lines, and hot-swaps the embedding
table when the pointer moves — requests in flight keep the table they
started with (no torn scores).

- ``server.py``   ScorerServer: verified load of the published step,
                  a pre-compiled [batch rung, L rung] shape ladder
                  (widths a doubling subset of the pipeline's
                  ``bucket_ladder``, so no request shape ever
                  recompiles), and an admission
                  queue that micro-batches concurrent requests under
                  ``serve_max_batch`` / ``serve_max_wait_ms``. Plus
                  the in-process ScoreClient tests and the soak use.
- ``reload.py``   ReloadWatcher: polls the pointer, verifies, swaps.
- ``frontend.py`` stdlib HTTP front end (POST /score, GET /healthz)
                  and the ``run_tffm.py serve`` driver.
"""

from fast_tffm_tpu.serve.server import (ScoreClient, ScoreResult,
                                        ScorerServer)

__all__ = ["ScorerServer", "ScoreClient", "ScoreResult"]
