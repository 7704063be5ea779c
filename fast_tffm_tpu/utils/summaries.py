"""TensorBoard scalar summaries — ``save_summaries_steps`` made real.

The reference inherits TF1 summary writing at a configured cadence
(SURVEY.md Appendix A, `save_summaries_steps`). Here the same knob
writes TensorBoard scalars (train loss, examples/sec, validation AUC)
as event files under ``<model_file>.tb/`` via TF's summary writer — TF
is an allowed utility dependency (SURVEY §7: data/AUC utilities, never
the model path). The import is lazy (TF costs ~25 s to load, paid only
when the knob is set) and failure-tolerant: without TF the knob warns
once and training proceeds.

Sync-safety: scalar values may be DEVICE arrays; they are buffered
as-is and fetched in one bulk ``jax.device_get`` at ``flush()``, called
from epoch boundaries, so summaries add no mid-stream device fetches up
to SUMMARY_BUFFER_MAX retained entries (a hot-loop scalar fetch stalls
async dispatch until the device has caught up, on any device). An epoch
longer than SUMMARY_BUFFER_MAX/2 sampled cadences pays one bulk
mid-epoch fetch per cap hit: the bound on retained device references is
the lesser evil, and README/config state the same caveat.
"""

from __future__ import annotations

import warnings
from typing import List, Optional, Tuple

from fast_tffm_tpu.config import FmConfig


# Buffered-scalar cap: device references retained between flushes. A
# tiny cadence on a months-long epoch must not retain unbounded device
# scalars; one rare mid-epoch sync is the lesser evil.
SUMMARY_BUFFER_MAX = 1024


class ScalarSummaries:
    """Buffered TensorBoard scalar writer (see module docstring)."""

    def __init__(self, logdir: str, tf_module):
        self._tf = tf_module
        self._writer = tf_module.summary.create_file_writer(logdir)
        self.logdir = logdir
        self._buf: List[Tuple[str, int, object]] = []

    def add(self, tag: str, step: int, value) -> None:
        """Queue one scalar; ``value`` may be a jax device array (not
        fetched here — see flush)."""
        self._buf.append((tag, step, value))
        if len(self._buf) >= SUMMARY_BUFFER_MAX:
            self.flush()

    def flush(self) -> None:
        if not self._buf:
            return
        # One device_get of the whole buffer; python-float values pass
        # through untouched.
        from fast_tffm_tpu.utils.fetch import bulk_fetch
        rows = []
        bulk_fetch([(v, (tag, step)) for tag, step, v in self._buf],
                   lambda v, meta: rows.append((meta[0], meta[1],
                                                float(v))))
        with self._writer.as_default():
            for tag, step, val in rows:
                self._tf.summary.scalar(tag, val, step=step)
        self._writer.flush()
        self._buf.clear()

    def close(self) -> None:
        self.flush()
        self._writer.close()


def make_summaries(cfg: FmConfig) -> Optional[ScalarSummaries]:
    """The train driver's summary sink: a ScalarSummaries under
    ``<model_file>.tb/`` when ``save_summaries_steps`` is set and TF is
    importable, else None (with one warning when the knob asked for
    summaries TF can't provide)."""
    if cfg.save_summaries_steps <= 0:
        return None
    try:
        import tensorflow as tf
    except Exception as e:  # pragma: no cover - env without TF
        warnings.warn(
            f"save_summaries_steps = {cfg.save_summaries_steps} needs "
            f"tensorflow for TensorBoard event files, which failed to "
            f"import ({type(e).__name__}); summaries are disabled for "
            "this run")
        return None
    return ScalarSummaries(cfg.model_file + ".tb", tf)
