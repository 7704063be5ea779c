"""A validating job's sweeps, kept on the device once they have been
placed (ISSUE 53).

The held-out files of a job are the same at every sweep and a sweep's
plane has no shuffle (``EpochFeed`` with ``training`` False), so sweep
n + 1's batches are, array for array, sweep n's. ``ResidentSweeps``
stands where the sweeps' ``EpochFeed`` stood (train.py ``sweep_feed``
returns it, ``evaluate()`` reads it and does not know the difference):
the first sweep passes through from the plane and is kept as it
passes; at its mark, if it may stay, the plane is closed and every
later sweep hands the kept ``(batch, placed)`` out again, in order,
with a mark behind the last. The scorer donates nothing, so the same
placed arrays are scored again: every score is bit for bit the
streamed sweep's.

A sweep may stay where all of this holds, each seen and none
configured:

- the feed places (``place`` given: one device or a mesh). A lookup
  backend's gather is the host's, nothing is placed to keep.
- no ``vocab``: an admit-mode eval view changes between sweeps.
- the sweep reached its mark: the files' end, or the session's cap,
  which cuts every sweep at the same batch.
- its placed bytes are within ``obs/memory.resident_sweep_budget()``:
  1/32 of one device's capacity, and no more than the device has left
  over its high-water mark when the job's first sweep opens (an epoch
  of steps has run by then: the mark holds the train step's peak). The
  references are dropped as the sum passes it, and the job streams
  every sweep as it did; the pre-flight books nothing for the sweep.
- the files are, by size and ``mtime_ns``, what they were before the
  kept sweep was read. They are looked at again as every sweep starts:
  a difference drops the kept sweep, and that sweep is read from a new
  plane, kept, and stays in its turn.

What the plane counts keeps its meaning, batches HANDED to a sweep:
the kept sweep's own increase of ``validation_plane/*`` (the registry
at its mark less the registry as its plane opened: the plane is held at
the mark, so nothing of a next sweep is in it) is added again at every
replayed sweep's mark. What is not done is not counted: the seconds
(``build_seconds``, the builders', ``validation/place_seconds``), the
plane's spans and ``validation_plane/epochs_fed_ahead``, which stays
the count of sweeps the PLANE fed ahead, stand still.
``validation/resident_sweeps`` counts the sweeps served from the
device, the gauge ``validation/resident_bytes`` and the ledger's owner
``resident_sweep`` say what is kept, and one log line a
job (one more where the files change) says which way it went and why."""

from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from fast_tffm_tpu.data.pipeline import (VALIDATION_PLANE, EpochFeed,
                                         EpochMark, expand_files)
from fast_tffm_tpu.obs.memory import (LEDGER, RESIDENT_SWEEP_OWNER,
                                      resident_sweep_budget)
from fast_tffm_tpu.obs.telemetry import active, batch_payload_bytes
from fast_tffm_tpu.utils.logging import get_logger

RESIDENT_SWEEPS = "validation/resident_sweeps"
RESIDENT_BYTES = "validation/resident_bytes"


def _mb(n: int) -> str:
    return f"{n / 1e9:.1f} GB" if n >= 1e9 else f"{n / 1e6:.0f} MB"


def files_signature(patterns: Sequence[str]) -> Tuple:
    """Size and ``mtime_ns`` of every file ``patterns`` name now (None
    for one that cannot be read: the plane's open says so loudly)."""
    out = []
    for path in expand_files(patterns):
        try:
            st = os.stat(path)
            out.append((path, st.st_size, st.st_mtime_ns))
        except OSError:
            out.append((path, None, None))
    return tuple(out)


def sweep_refusal(places: bool, view: bool) -> Optional[str]:
    """Why no sweep of a job can stay on the device whatever its size
    (the holder's words, and ``obs/memory.plan``'s from the config),
    else None. ``places``: the sweeps' plane places its batches for
    the scorer (no lookup backend); ``view``: its batches are an
    admit-mode vocab's eval view's."""
    return ("admit-mode view" if view else
            None if places else "host lookup")


def _handed(counters) -> Dict[str, float]:
    """What the sweeps' plane has counted of batches handed out: its
    counters less the seconds (nothing is built or placed for a batch
    handed out again) and the sweeps it fed ahead."""
    return {k: v for k, v in counters.items()
            if k.startswith(VALIDATION_PLANE + "/")
            and not k.endswith("_seconds")
            and not k.endswith("/epochs_fed_ahead")}


class ResidentSweeps:
    """The sweeps' feed, with ``EpochFeed``'s consumer side: ``next()``
    gives ``(batch, placed)`` and, in band, an ``EpochMark`` behind each
    sweep's last batch; ``marked``; ``release()``; ``close()``.

    ``plane(sweeps)``: an ``EpochFeed`` of those sweeps, as the session
    wants it. ``files``: every pattern the plane reads (weight sidecars
    too). ``places``, ``view``: ``sweep_refusal``'s."""

    def __init__(self, plane: Callable[[range], EpochFeed], sweeps: range,
                 files: Sequence[str], places: bool = True,
                 view: bool = False):
        self._plane, self._sweeps, self._files = plane, sweeps, tuple(files)
        # Why no sweep of this job can stay, while that is so.
        self._refusal = refusal = sweep_refusal(places, view)
        self._log = get_logger()
        self._tel = active()
        if self._tel is not None:
            self._tel.count(RESIDENT_SWEEPS, 0)
        self.marked = sweeps.start - 1
        self._kept: Optional[List[tuple]] = None   # the sweep, once whole
        self._stats = None                         # its mark's
        self._counted: Dict[str, float] = {}       # what its plane counted
        self._base: Dict[str, float] = {}          # ... from here on
        self._at = 0                               # the next one to replay
        self._taking: Optional[List[tuple]] = None  # the sweep passing by
        self._bytes = self._budget = 0
        self._read_from: Tuple = ()    # the files before that sweep's read
        self._starting = True
        self._feed: Optional[EpochFeed] = None
        if refusal is not None and self._to_come() > 1:
            self._log.info("validation sweeps streamed: %s", refusal)
        self._open()

    # -- the consumer's side ---------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self._starting:
            self._start()
            self._starting = False
        if self._kept is not None:
            return self._replay()
        if self._feed is None:  # closed
            raise StopIteration
        item = next(self._feed)
        if isinstance(item, EpochMark):
            self.marked = item.epoch
            self._starting = True
            self._decide(item)
        elif self._taking is not None:
            self._take(item)
        return item

    def release(self, epoch: int) -> None:
        if self._feed is not None:
            self._feed.release(epoch)

    def close(self) -> None:
        """The plane's threads end, the kept arrays are let go and the
        ledger's entry goes with them."""
        self._drop()
        self._taking = None
        feed, self._feed = self._feed, None
        if feed is not None:
            feed.close()

    # -- a sweep passing through -------------------------------------------

    def _to_come(self) -> int:
        return self._sweeps.stop - (self.marked + 1)

    def _open(self) -> None:
        """A plane for the sweeps still to come, the first of them kept
        as it passes if one can stay and there is one behind it to
        score it again (``evaluate()``'s own feed of one sweep, a job's
        last epoch: nothing to keep)."""
        if self._refusal is None and self._to_come() > 1:
            self._read_from = files_signature(self._files)
            self._taking, self._bytes = [], 0
            self._budget = resident_sweep_budget()
            self._base = self._plane_counts()
        self._feed = self._plane(range(self.marked + 1, self._sweeps.stop))

    def _plane_counts(self) -> Dict[str, float]:
        if self._tel is None:
            return {}
        return _handed(self._tel.registry.snapshot()["counters"])

    def _take(self, item) -> None:
        batch, placed = item
        self._bytes += batch_payload_bytes(placed)
        if self._bytes > self._budget:
            self._taking = []   # over: only the sum goes on, for the log
        else:
            self._taking.append((batch, placed))

    def _decide(self, mark: EpochMark) -> None:
        """At a streamed sweep's mark, before the release that would
        have the plane cut the next one: stay, or stream on."""
        if self._taking is None:
            return
        budget, now = self._budget, files_signature(self._files)
        counts = self._plane_counts()
        if self._bytes > budget:
            self._taking, self._refusal = None, "over the budget"
            self._log.info(
                "validation sweeps streamed: %s over the budget of %s (%s)",
                _mb(self._bytes), _mb(budget),
                "a sweep's share of the device"
                if budget == resident_sweep_budget(used=0)
                else "half of what the device has left")
        elif now != self._read_from:
            # rewritten while they were read: the next sweep is kept
            self._read_from, self._bytes, self._base = now, 0, counts
            self._taking = [] if self._to_come() > 1 else None
            self._log.info("validation sweeps streamed: files changed")
        else:
            self._kept, self._taking = self._taking, None
            self._stats, self._at = mark.stats, 0
            self._counted = {k: v - self._base.get(k, 0)
                             for k, v in counts.items()}
            self._feed.close()
            self._feed = None
            LEDGER.register(RESIDENT_SWEEP_OWNER, self._bytes)
            if self._tel is not None:
                self._tel.set(RESIDENT_BYTES, float(self._bytes))
            self._log.info("validation sweeps resident: %d batches, %s on "
                           "the device (budget %s)", len(self._kept),
                           _mb(self._bytes), _mb(budget))

    # -- a sweep served from the device -------------------------------------

    def _start(self) -> None:
        if self._kept is None:
            return
        if self._to_come() < 1:
            raise StopIteration
        if files_signature(self._files) != self._read_from:
            self._log.info("validation sweeps streamed: files changed")
            self._drop()
            self._open()

    def _replay(self):
        if self._at < len(self._kept):
            item = self._kept[self._at]
            self._at += 1
            return item
        self._at, self._starting = 0, True
        self.marked += 1
        if self._tel is not None:
            self._tel.count(RESIDENT_SWEEPS)
            for name, n in self._counted.items():
                self._tel.count(name, n)
        return EpochMark(self.marked, self._stats)

    def _drop(self) -> None:
        if self._kept is not None:
            self._kept = self._stats = None
            LEDGER.release(RESIDENT_SWEEP_OWNER)
            if self._tel is not None:
                self._tel.set(RESIDENT_BYTES, 0.0)
