"""The plain reference of ``run_mode = stream``'s guarantees (README
"correct", the stream checks): what a clean single pass over the
concatenated ledger yields, and the watermark that must hold after any
count of stepped batches. NumPy and the standard library; nothing of
``fast_tffm_tpu/data/stream.py`` is imported.

The ledger is the sorted names of one discovery (every shard of the
benchmark is there, sealed, before ``train()`` is called). A shard's
lines are those of its SEALED bytes: every newline-terminated line and,
in a sealed shard, a last line that lacks its newline; the torn tail of
an unsealed shard is held back, and nothing behind an unsealed shard is
read (the stream is a log). Every line is an example: the generators
write no blank line. A batch is the next ``batch_size`` lines, whatever
shard boundaries fall inside it.

What it gives, from the generator's own record (``Corpus.signatures()``,
one 64-bit signature a line) and the shards' bytes:

- ``batch(i)``: the signatures of batch ``i``, in line order;
- ``twice_or_never(fed, n)``: over the first ``n`` batches, the lines
  fed other than once (as a multiset of signatures);
- ``watermark(n)``: per shard, in ledger order, the lines and bytes
  consumed after ``n`` batches, and ``watermark_off`` compares a
  payload of the program's (``StepLoop.stream_watermark``) with it.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np


def line_ends(data: bytes, sealed: bool = True) -> np.ndarray:
    """Byte offset just past each line of ``data``, the lines a stream
    may consume: the newline-terminated ones and, in a sealed shard, a
    last line without its newline (its offset is the shard's size)."""
    ends = np.flatnonzero(np.frombuffer(data, dtype=np.uint8) == 10) + 1
    if sealed and len(data) and (len(ends) == 0 or ends[-1] != len(data)):
        ends = np.append(ends, len(data))
    return ends.astype(np.int64)


@dataclasses.dataclass
class Shard:
    """One file of the stream: the name the ledger lists it under, the
    signature of each of its lines (file order) and the byte offset
    past each (``line_ends``); only the lines a stream may consume."""
    path: str
    signatures: np.ndarray      # uint64 [lines]
    ends: np.ndarray            # int64 [lines]
    sealed: bool = True


class StreamReference:
    def __init__(self, shards: Sequence[Shard], batch_size: int):
        self.B = int(batch_size)
        # The ledger: sorted names of one discovery. Nothing behind an
        # unsealed shard is read.
        ledger = sorted(shards, key=lambda s: s.path)
        self.ledger: List[Shard] = ledger
        readable = []
        for s in ledger:
            if len(s.signatures) != len(s.ends):
                raise ValueError(f"{s.path}: a signature a line")
            readable.append(s)
            if not s.sealed:
                break
        self._starts = np.concatenate(
            [[0], np.cumsum([len(s.ends) for s in readable])]
        ).astype(np.int64)
        self._readable = readable
        self.lines = int(self._starts[-1])
        self.batches = self.lines // self.B     # whole batches alone

    def _span(self, first: int, n: int) -> np.ndarray:
        """Signatures of ledger lines ``[first, first + n)``."""
        out, last = [], first + n
        lo = int(np.searchsorted(self._starts, first, side="right")) - 1
        for k in range(lo, len(self._readable)):
            a, b = int(self._starts[k]), int(self._starts[k + 1])
            if a >= last:
                break
            out.append(self._readable[k].signatures[
                max(first, a) - a:min(last, b) - a])
        return (np.concatenate(out) if out
                else np.zeros(0, dtype=np.uint64))

    def batch(self, i: int) -> np.ndarray:
        if not 0 <= i < self.batches:
            raise IndexError(f"the ledger holds {self.batches} whole "
                             f"batches; batch {i} asked for")
        return self._span(i * self.B, self.B)

    def not_in_ledger_order(self, fed: Sequence[np.ndarray]) -> int:
        """Of the fed batches (each the signatures of its examples, in
        the order they were fed; an example not trained on reads 0),
        those that are not the reference's batch of the same index."""
        return sum(1 for i, sig in enumerate(fed)
                   if i >= self.batches
                   or not np.array_equal(np.asarray(sig), self.batch(i)))

    def twice_or_never(self, fed: Sequence[np.ndarray]) -> int:
        """Over ``len(fed)`` batches: lines of the reference's prefix
        that were fed other than once, and fed examples that are no
        line of it, counted as a multiset of signatures (a corpus
        listed several times holds each of its lines as often)."""
        n = min(len(fed), self.batches)
        want = self._span(0, n * self.B)
        got = (np.concatenate([np.asarray(s) for s in fed]) if len(fed)
               else np.zeros(0, dtype=np.uint64))
        got = got[got != 0]
        keys, inv = np.unique(np.concatenate([want, got]),
                              return_inverse=True)
        count = np.zeros((2, len(keys)), dtype=np.int64)
        np.add.at(count[0], inv[:len(want)], 1)
        np.add.at(count[1], inv[len(want):], 1)
        return int(np.abs(count[0] - count[1]).sum())

    def watermark(self, n_batches: int) -> List[dict]:
        """Per shard of the whole ledger, in ledger order: lines and
        bytes consumed once ``n_batches`` batches are stepped."""
        done = min(int(n_batches), self.batches) * self.B
        out = []
        for k, s in enumerate(self.ledger):
            lines = 0
            if k < len(self._readable):
                lines = int(np.clip(done - self._starts[k], 0, len(s.ends)))
            out.append({"path": s.path, "lines": lines,
                        "bytes": int(s.ends[lines - 1]) if lines else 0,
                        "sealed": bool(s.sealed)})
        return out

    def watermark_off(self, payload, n_batches: int) -> int:
        """The program's adopted watermark against ``watermark(n)``:
        the summed absolute difference in lines and in bytes, shard by
        shard in ledger order. A shard the payload lacks, or lists
        under another name, counts all the reference's and all the
        payload's lines and bytes there, and one more."""
        files = list((payload or {}).get("files", ()))
        want = self.watermark(n_batches)
        off = 0
        for k in range(max(len(files), len(want))):
            w = want[k] if k < len(want) else None
            f = files[k] if k < len(files) else None
            if w is None or f is None or f["path"] != w["path"]:
                off += 1 + sum(int(x[key]) for x in (w, f) if x
                               for key in ("lines", "bytes"))
                continue
            off += (abs(int(f["lines"]) - w["lines"])
                    + abs(int(f["bytes"]) - w["bytes"]))
        return off
