"""Per-publish model-quality evaluation + the publish gate.

The closed-loop layer (README "SLOs & quality gate"): a streaming
trainer with ``validation_files`` runs one validation sweep at every
publish settle — the synchronization point that already exists
(checkpoint save + manifest verify) — and the sweep's quality numbers
both land in the metrics stream (``quality/auc`` / ``quality/loss`` /
``quality/calibration`` gauges under a ``quality/eval`` span) and gate
the ``published`` pointer: when validation regressed past the
``publish_min_auc`` / ``publish_max_auc_drop`` thresholds the pointer
does NOT move, a ``health: gate_held`` event fires, and fmstat's
verdict reads GATE-HELD. A bad data burst can therefore never reach
serving — scorers keep hot-reloading the last PASSING step while the
trainer keeps consuming (and, once the data heals, a later publish
passes and the loop closes again).

Zero-added-fetch contract: ``QualityStats`` is fed the SAME host score
chunks the validation AUC update consumes (``train.evaluate`` passes
it into its ChunkedFetcher callback; the lockstep path folds its four
sums into the existing AUC-histogram allgather payload), so the
quality loop introduces no device fetch beyond the sweep's own D2H —
the same sync-safety discipline as the rest of obs/.

Multi-host: every worker computes the same deterministic decision from
the same merged AUC, and the chief's decision is additionally
broadcast (``data/stream.broadcast_blob`` — identity single-process)
so the pointer move and the baseline update are broadcast-identical by
construction, never by coincidence.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

# Probability clip for the logistic log-loss: a saturated score must
# cost a large-but-finite loss, not an inf that poisons the mean.
LOGLOSS_EPS = 1e-7

# Payload width QualityStats contributes to the lockstep AUC merge
# (loss_sum, weight_sum, pred_sum, label_sum).
SUMS_WIDTH = 4


class QualityStats:
    """Mergeable accumulator for the per-publish quality gauges.

    ``update(scores, labels, weights)`` consumes the raw (pre-sigmoid)
    host score chunks the validation sweep already fetched; ``sums()``
    / ``load_sums()`` are the fixed-width merge surface the lockstep
    path ships inside its existing allgather payload."""

    def __init__(self, loss_type: str = "logistic"):
        self.loss_type = loss_type
        self.loss_sum = 0.0
        self.weight_sum = 0.0
        self.pred_sum = 0.0
        self.label_sum = 0.0

    def update(self, scores, labels, weights) -> None:
        # The scorer's own overflow-stable sigmoid (metrics.py) — a
        # saturated logit chunk must not spray exp-overflow warnings,
        # and the gate's probability must be THE serving probability.
        from fast_tffm_tpu.metrics import sigmoid
        s = np.asarray(scores, dtype=np.float64)
        y = np.asarray(labels, dtype=np.float64)
        w = np.asarray(weights, dtype=np.float64)
        if self.loss_type == "logistic":
            p = sigmoid(s)
            pc = np.clip(p, LOGLOSS_EPS, 1.0 - LOGLOSS_EPS)
            loss = -(y * np.log(pc) + (1.0 - y) * np.log(1.0 - pc))
        else:  # mse: the "prediction" is the raw score itself
            p = s
            loss = (s - y) ** 2
        self.loss_sum += float((w * loss).sum())
        self.weight_sum += float(w.sum())
        self.pred_sum += float((w * p).sum())
        self.label_sum += float((w * y).sum())

    def sums(self) -> np.ndarray:
        return np.asarray([self.loss_sum, self.weight_sum,
                           self.pred_sum, self.label_sum], np.float64)

    def load_sums(self, vals) -> None:
        """Replace the local sums with merged (cross-worker) totals —
        the tail of the lockstep AUC-merge payload."""
        vals = np.asarray(vals, dtype=np.float64).reshape(-1)
        if vals.shape[0] != SUMS_WIDTH:
            raise ValueError(
                f"quality sums payload must have {SUMS_WIDTH} values, "
                f"got {vals.shape[0]}")
        self.loss_sum, self.weight_sum, self.pred_sum, self.label_sum = (
            float(v) for v in vals)

    @property
    def loss(self) -> Optional[float]:
        """Weighted mean validation loss (log-loss for logistic, MSE
        for mse), or None on an empty sweep."""
        if self.weight_sum <= 0:
            return None
        return self.loss_sum / self.weight_sum

    @property
    def calibration(self) -> Optional[float]:
        """Sum(predicted) / sum(label) — 1.0 is perfectly calibrated,
        >1 over-predicts. None when the sweep held no positive mass
        (the ratio is undefined, not infinite)."""
        if self.label_sum <= 0:
            return None
        return self.pred_sum / self.label_sum


class PublishGate:
    """The per-publish quality gate's decision state.

    ``decide(auc, step)`` is PURE (no state mutation) and returns a
    JSON-safe decision dict, so the chief's decision can ride
    ``broadcast_blob`` verbatim and every worker applies the identical
    outcome; ``note_published(auc)`` advances the baseline only after
    a publish actually landed. On the very first publish no baseline
    exists yet, so only ``publish_min_auc`` applies — the documented
    first-publish contract."""

    def __init__(self, min_auc: float = 0.0, max_drop: float = 0.0):
        self.min_auc = float(min_auc)
        self.max_drop = float(max_drop)
        # AUC of the last SUCCESSFUL publish; None until one lands.
        self.baseline: Optional[float] = None

    @classmethod
    def from_config(cls, cfg) -> Optional["PublishGate"]:
        min_auc = float(getattr(cfg, "publish_min_auc", 0.0))
        max_drop = float(getattr(cfg, "publish_max_auc_drop", 0.0))
        if not min_auc and not max_drop:
            return None
        return cls(min_auc=min_auc, max_drop=max_drop)

    def decide(self, auc: float, step: int) -> Dict[str, Any]:
        auc = float(auc)
        reasons = []
        # A non-finite AUC (empty or single-class validation sweep)
        # HOLDS any configured gate outright — including a
        # max_drop-only gate on its very first publish, where neither
        # threshold comparison below would fire: an unevaluable model
        # must never publish through a gate.
        if not np.isfinite(auc):
            reasons.append(
                f"validation AUC is {auc} (empty or single-class "
                "sweep): a configured gate never passes an "
                "unevaluable model")
        if self.min_auc and not auc >= self.min_auc:
            reasons.append(
                f"AUC {auc:.6f} below publish_min_auc {self.min_auc}")
        if (self.max_drop and self.baseline is not None
                and not auc >= self.baseline - self.max_drop):
            reasons.append(
                f"AUC {auc:.6f} dropped {self.baseline - auc:.6f} from "
                f"the last published {self.baseline:.6f} "
                f"(publish_max_auc_drop {self.max_drop})")
        return {
            "held": bool(reasons),
            "step": int(step),
            "auc": auc,
            "baseline": self.baseline,
            "min_auc": self.min_auc,
            "max_auc_drop": self.max_drop,
            "reasons": reasons,
        }

    def note_published(self, auc: Optional[float]) -> None:
        """Record a LANDED publish's AUC as the next drop baseline.
        Non-finite values never become a baseline (a NaN baseline
        would disarm the drop check forever)."""
        if auc is not None and np.isfinite(auc):
            self.baseline = float(auc)


def emit_gate_held(tel, decision: Dict[str, Any]) -> None:
    """The gate's durable evidence: a ``health: gate_held`` event +
    ``quality/gate_held`` counter, flushed straight to disk — the
    stream keeps running, but the operator's fmstat view (and the
    soak's assertions) must see the hold NOW, not at the next barrier.
    No-op without telemetry."""
    if tel is None:
        return
    tel.count("quality/gate_held")
    tel.sink.emit("health", {
        "status": "gate_held",
        "step": int(decision.get("step", -1)),
        "auc": decision.get("auc"),
        "baseline": decision.get("baseline"),
        "reasons": list(decision.get("reasons") or []),
    })
    tel.sink.flush()


def emit_quality(tel, step: int, auc: float, stats: QualityStats,
                 n_examples: int) -> None:
    """The sweep's metrics-side landing: gauges + counters + one
    timeline scalar, all plain host floats (the zero-added-fetch
    contract — everything here was computed from already-fetched score
    chunks). Sets ``validation/auc`` too: the quality sweep IS this
    stream's validation pass. (``quality/eval_seconds`` is counted by
    the ``quality/eval`` span around the sweep.)"""
    if tel is None:
        return
    tel.count("quality/evals")
    tel.count("quality/examples", float(n_examples))
    tel.set("quality/auc", float(auc))
    tel.set("validation/auc", float(auc))
    if stats.loss is not None:
        tel.set("quality/loss", float(stats.loss))
    if stats.calibration is not None:
        tel.set("quality/calibration", float(stats.calibration))
    # fmlint: disable=R001 -- auc is a host float from the streamed
    # AUC merge, never a device array
    tel.add_scalar("quality/auc", int(step), float(auc))
