"""The benchmark's seeded libsvm corpus: per-field Zipf ids over a
configuration's real cardinalities, written vectorised as fixed-width
text, with the generator's own record of what every line means (hashed
table rows, values, fields, labels) so that the reference needs
nothing the program parsed.

Copied in spirit from fast_tffm_tpu/data/synth.py (Zipf a = 1.35,
log-normal numerics, string tokens through MurmurHash64A) with the
cardinalities as data and the Python line loop replaced by array
writes: synth.generate writes 13.5k lines/s, this writes several
hundred thousand.

Line formats (fast_tffm_tpu/data/parser.py):
    FM :  <label> I01:<d.ddd> ... C01=<8 hex> ...
    FFM:  <label> <ff>:I01:<d.ddd> ... <ff>:C01=<8 hex> ...
The feature id is the token up to the value's colon ("I01",
"C01=0000abcd"); categorical values are 1.0 and left out, as in
Criteo/Avazu conversions."""

from __future__ import annotations

import dataclasses
import os
from typing import List, Sequence

import numpy as np

_HEX = np.frombuffer(b"0123456789abcdef", dtype=np.uint8)
_M = np.uint64(0xC6A4A7935BD1E995)
_R = np.uint64(47)


def murmur64_fixed(tokens: np.ndarray, seed: int = 0) -> np.ndarray:
    """MurmurHash64A (little-endian, seed 0: data/hashing.py's and
    _parser.cc's hash) of n equal-length byte strings, uint8 [n, len]
    -> uint64 [n]."""
    tokens = np.ascontiguousarray(tokens, dtype=np.uint8)
    n, ln = tokens.shape
    with np.errstate(over="ignore"):
        h = np.full(n, np.uint64(seed) ^ (np.uint64(ln) * _M),
                    dtype=np.uint64)
        nb = ln // 8
        if nb:
            blocks = tokens[:, :nb * 8].reshape(n, nb, 8)
            ks = np.zeros((n, nb), dtype=np.uint64)
            for b in range(8):
                ks |= blocks[:, :, b].astype(np.uint64) << np.uint64(8 * b)
            for i in range(nb):
                k = ks[:, i] * _M
                k ^= k >> _R
                k *= _M
                h ^= k
                h *= _M
        tail = tokens[:, nb * 8:]
        if tail.shape[1]:
            t = np.zeros(n, dtype=np.uint64)
            for b in range(tail.shape[1]):
                t |= tail[:, b].astype(np.uint64) << np.uint64(8 * b)
            h ^= t
            h *= _M
        h ^= h >> _R
        h *= _M
        h ^= h >> _R
    return h


@dataclasses.dataclass
class Corpus:
    """What the generator knows of its lines: per line the hashed table
    rows, values (thousandths, so that text and arrays agree exactly)
    and fields of its L features, and the label."""
    labels: np.ndarray       # uint8 [n]
    rows: np.ndarray         # int64 [n, L] table rows after hashing
    millis: np.ndarray       # int32 [n, L] value * 1000
    fields: np.ndarray       # int32 [L] field of each feature slot
    files: List[str]
    lines_per_file: List[int]

    @property
    def vals(self) -> np.ndarray:
        return self.millis.astype(np.float64) / 1000.0

    def signatures(self) -> np.ndarray:
        return example_signatures(self.labels, self.rows, self.millis)


def listed(files: Sequence[str], passes: int) -> List[str]:
    """The corpus listed ``passes`` times over, as a job lists the
    parts of a long log: the files themselves, then links to them
    under names of their own (predict() names a score file after its
    input). One epoch, or one sweep, is then ``passes`` corpora long
    at no cost in set-up."""
    out = list(files)
    for p in range(1, int(passes)):
        for f in files:
            link = os.path.join(os.path.dirname(f),
                                f"pass{p:02d}-{os.path.basename(f)}")
            if os.path.lexists(link):
                os.remove(link)
            os.symlink(os.path.basename(f), link)
            out.append(link)
    return out


def example_signatures(labels, rows, millis) -> np.ndarray:
    """Order-free 64-bit signature of each example's (label, {(row,
    value)}): what matches a fed example to the corpus line it was
    parsed from. Cells with value 0 (padding) add nothing."""
    rows = np.asarray(rows).astype(np.uint64)
    m = np.asarray(millis).astype(np.uint64)
    with np.errstate(over="ignore"):
        x = (rows * np.uint64(0x9E3779B97F4A7C15)) ^ (m * _M)
        x ^= x >> np.uint64(29)
        x *= np.uint64(0xBF58476D1CE4E5B9)
        x ^= x >> np.uint64(32)
        x = np.where(m == 0, np.uint64(0), x)
        return x.sum(axis=1) + np.asarray(labels).astype(np.uint64)


def generate(features: dict, model_type: str, vocabulary_size: int,
             n_lines: int, seed: int, out_dir: str, n_files: int,
             prefix: str) -> Corpus:
    """Draw ``n_lines`` examples from ``seed`` and write them as
    ``n_files`` libsvm files under ``out_dir``."""
    cards = [int(c) for c in features["categorical_cardinalities"]]
    n_num = int(features.get("numeric", 0))
    zipf_a = float(features["zipf_a"])
    ffm = model_type == "ffm"
    rng = np.random.default_rng([int(seed), 0xC0FFEE])
    n = int(n_lines)
    L = n_num + len(cards)
    labels = (rng.random(n) < float(features["positive_rate"])
              ).astype(np.uint8)
    millis = np.full((n, L), 1000, dtype=np.int32)
    if n_num:
        counts = rng.lognormal(mean=1.0, sigma=1.2, size=(n, n_num))
        millis[:, :n_num] = np.clip(
            np.rint(1000.0 * np.log1p(counts)), 1, 9999).astype(np.int32)
    ids = np.empty((n, len(cards)), dtype=np.int64)
    for f, card in enumerate(cards):
        ids[:, f] = (rng.zipf(zipf_a, size=n) - 1) % card

    # One template line, tiled; then the digit columns are filled.
    tmpl = bytearray(b"0")
    slots = []   # (feature slot, fid start, value/hex start, fid end)
    for j in range(L):
        tmpl += b" "
        if ffm:
            tmpl += b"%02d:" % j
        a = len(tmpl)
        if j < n_num:
            tmpl += b"I%02d" % (j + 1)
            b = len(tmpl)
            tmpl += b":"
            slots.append((j, a, len(tmpl), b))
            tmpl += b"0.000"
        else:
            tmpl += b"C%02d=" % (j - n_num + 1)
            slots.append((j, a, len(tmpl), len(tmpl) + 8))
            tmpl += b"00000000"
    tmpl += b"\n"
    # Built transposed ([width, n]) so that each digit column is one
    # contiguous write; transposed once at the end.
    text = np.empty((len(tmpl), n), dtype=np.uint8)
    text[:] = np.frombuffer(bytes(tmpl), dtype=np.uint8)[:, None]
    text[0] = 48 + labels
    rows = np.empty((n, L), dtype=np.int64)
    vs = np.uint64(vocabulary_size)
    for j, a, v0, b in slots:
        if j < n_num:
            m = millis[:, j]
            text[v0] = 48 + m // 1000
            text[v0 + 2] = 48 + (m // 100) % 10
            text[v0 + 3] = 48 + (m // 10) % 10
            text[v0 + 4] = 48 + m % 10
            rows[:, j] = int(murmur64_fixed(text[a:b, :1].T)[0] % vs)
        else:
            col = ids[:, j - n_num]
            for p in range(8):
                text[v0 + p] = _HEX[(col >> (4 * (7 - p))) & 15]
            rows[:, j] = (murmur64_fixed(text[a:b].T) % vs).astype(
                np.int64)
    text = np.ascontiguousarray(text.T)
    os.makedirs(out_dir, exist_ok=True)
    files, per_file = [], []
    bounds = np.linspace(0, n, n_files + 1).astype(int)
    for i in range(n_files):
        path = os.path.join(out_dir, f"{prefix}-{i:03d}.libsvm")
        text[bounds[i]:bounds[i + 1]].tofile(path)
        files.append(path)
        per_file.append(int(bounds[i + 1] - bounds[i]))
    fields = (np.arange(L, dtype=np.int32) if ffm
              else np.zeros(L, dtype=np.int32))
    return Corpus(labels=labels, rows=rows, millis=millis, fields=fields,
                  files=files, lines_per_file=per_file)
