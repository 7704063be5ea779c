"""Regime-aware kernel selection for the 2nd-order FM scorer.

``kernel = auto`` used to resolve unconditionally to the fused Pallas
kernel on TPU. The matrix below is the rule in force, but it was taken
on an EARLIER device (same-window interleaved pairs, k=8, B=8192; the
record was removed in PR 21) and is UNVERIFIED on the v5e: PR 21 only
established that the kernel compiles under Mosaic there and matches
the XLA path's loss. ROADMAP D4 re-measures it or deletes the kernel.
It says the winner depends on (L, dedup), not the backend alone:

    L   dedup    Pallas  XLA    Pallas/XLA
    48  device   302M    450M   0.67x
    48  host     422M    450M   0.94x
    64  host     360M    413M   0.87x
    64  device   450M    316M   1.42x

Pallas only wins where the device-side unique pass keeps the batch's
rows hot in VMEM AND the bucket is at least a full 64-lane tile; every
host-dedup cell and the sub-tile L=48 cell measured XLA faster (the
k=16 check at the bench shape agreed: 363M vs 406M). So auto picks
Pallas exactly in the measured winning regime and XLA elsewhere —
per BUCKET, at trace time: the bucketed pipeline compiles one
executable per (spec, L) anyway, so different buckets of one job can
(correctly) run different kernels.

Consequence worth stating: mesh and multi-process paths REQUIRE host
dedup, so under auto they always resolve to XLA (the matrix's two
host-dedup cells both measured XLA faster). That cell pair was
measured single-chip — the sharded-assembly regime itself has no
direct measurement — so a cluster operator who measures otherwise can
still force ``kernel = pallas`` (it runs under shard_map).

Who is under which dedup moved since the pairs were taken. A one-chip
train step has run the host unique since PR 26 (on the v5e XLA 1.26 ms
against Pallas 1.73 there, B=8192, K=16, L=64), and since PR 45 so do
one-chip sweeps (``predict``, validation: ``dedup = auto`` is the host
unique whatever the use, models/fm.ModelSpec.from_config): a sweep
over buckets of L >= 64 changed from Pallas to XLA with its wire. The
(device, L >= 64) cell is left to serve, which forces raw ids itself
(scoring.CompiledScorer(dedup="device")), and to an explicit ``dedup =
device``. The matrix is as it was.

The XLA column also predates PR 42: ``fm_batch_scores`` then sliced
the w column off the expanded rows and re-laid w and v apart, which on
the v5e cost the forward a slice and a second layout copy (0.73 ms at
``[8192, 40, 17]``; PERF.md section 6, PR 42). The XLA path it names
is faster now than when these pairs were taken; the rule is as it was.

Re-measure with ``python tools/kernel_probe.py`` (interleaved A/B at
your shapes) and,
if the regime boundary moved, override per job with ``kernel =
pallas|xla`` — the config knob always beats the matrix.
"""

from __future__ import annotations


def auto_kernel(dedup: str, L: int) -> str:
    """Resolve ``kernel = auto`` for a 2nd-order FM bucket of width
    ``L`` under ``dedup`` mode. Callers guarantee model_type=fm,
    order=2, TPU backend (ModelSpec.from_config keeps 'auto' only
    there)."""
    return "pallas" if dedup == "device" and L >= 64 else "xla"
