"""Regime-aware kernel selection for the 2nd-order FM scorer.

The rule: ``kernel = auto`` is the fused Pallas kernel where the batch
ships raw ids (``dedup = device``) AND the bucket is at least a full
64-lane tile, and XLA everywhere else: per BUCKET, at trace time, so
different buckets of one job can run different kernels. Mesh and
multi-process paths require the host unique, so under auto they are
always XLA; the config knob (``kernel = pallas|xla``) beats the rule.

Where it was measured: interleaved pairs at k=8, B=8192, L in {48, 64},
both dedup modes, on an EARLIER device (Pallas ahead only at (device,
64): 1.42x; behind at the other three: 0.67x to 0.94x). On the v5e only
the host-unique cell has been taken (XLA ahead: PERF.md section 6,
PR 26); the (device, L >= 64) cell, which serve runs, has not. ROADMAP
D4 re-measures it with ``python tools/kernel_probe.py`` or deletes the
kernel.
"""

from __future__ import annotations


def auto_kernel(dedup: str, L: int) -> str:
    """Resolve ``kernel = auto`` for a 2nd-order FM bucket of width
    ``L`` under ``dedup`` mode. Callers guarantee model_type=fm,
    order=2, TPU backend (ModelSpec.from_config keeps 'auto' only
    there)."""
    return "pallas" if dedup == "device" and L >= 64 else "xla"
