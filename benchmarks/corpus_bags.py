"""A seeded libsvm corpus whose lines have single-valued ids AND bags
of tokens, so that no two lines need be as long as each other: search-ad
logs with text (KDD Cup 2012 Track 2: twelve ids an impression, and the
words of its query, keyword, ad title and ad description), as libFM
takes them: an id one-hot, a bag a set-valued group whose cells carry
1/len.

Line format (fast_tffm_tpu/data/parser.py):

    <label> C01=<8 hex> ... C12=<8 hex> W=<8 hex>:<d.ddd> ...

A word is ONE feature whichever bag holds it (the track's four token
files share a vocabulary); a word drawn twice is two cells. A bag of
``n`` words gives each cell ``round(1000 / n) / 1000``, so that text and
arrays agree to the thousandth, as corpus.py's numerics do.

``generate`` has corpus.generate's signature and gives corpus.Corpus, so
it stands in that function's place for a call (``in_place_of_generate``;
drivers/train_bags.py, control_bags.py). The record is a padded
rectangle ``[n, width]``, ``width`` the sum of the caps: a pad cell is
row 0 with ``millis`` 0, which ``example_signatures`` and
``ReferenceTrainer.step`` skip. Written with array operations alone: no
Python loop runs over lines or tokens."""

from __future__ import annotations

import contextlib
import os

import numpy as np

from benchmarks import corpus as corpus_mod
from benchmarks.corpus import _HEX, Corpus, murmur64_fixed

_ID_TOKEN = 13          # " Cnn=" + 8 hex
_WORD_TOKEN = 17        # " W=" + 8 hex + ":" + "d.ddd"


def _hex8(col: np.ndarray) -> np.ndarray:
    """uint8 [8, n]: the eight lower-case hex digits of each value."""
    return np.stack([_HEX[(col >> (4 * (7 - p))) & 15] for p in range(8)])


def width(features: dict) -> int:
    """The most cells a line can have: the ids and every bag full."""
    return len(features["id_cardinalities"]) + sum(
        int(b["cap"]) for b in features["bags"])


def generate(features: dict, model_type: str, vocabulary_size: int,
             n_lines: int, seed: int, out_dir: str, n_files: int,
             prefix: str) -> Corpus:
    """Draw ``n_lines`` examples from ``seed`` and write them as
    ``n_files`` libsvm files under ``out_dir``."""
    if model_type != "fm":
        raise ValueError("a bag's words have no field of their own: this "
                         f"corpus is FM's, not {model_type!r}'s")
    cards = [int(c) for c in features["id_cardinalities"]]
    bags = features["bags"]
    zipf_a = float(features["zipf_a"])
    words = int(features["bag_vocabulary"])
    rng = np.random.default_rng([int(seed), 0xBA65])
    n, n_ids, W = int(n_lines), len(cards), width(features)
    vs = np.uint64(vocabulary_size)
    labels = (rng.random(n) < float(features["positive_rate"])
              ).astype(np.uint8)

    # The head of every line: label and ids, fixed width (corpus.py's way).
    tmpl = bytearray(b"0")
    for j in range(n_ids):
        tmpl += b" C%02d=00000000" % (j + 1)
    head = np.empty((len(tmpl), n), dtype=np.uint8)
    head[:] = np.frombuffer(bytes(tmpl), dtype=np.uint8)[:, None]
    head[0] = 48 + labels
    rows = np.zeros((n, W), dtype=np.int64)
    millis = np.zeros((n, W), dtype=np.int32)
    millis[:, :n_ids] = 1000
    for j, card in enumerate(cards):
        a = 1 + _ID_TOKEN * j + 1            # past the token's space
        head[a + 4:a + 12] = _hex8((rng.zipf(zipf_a, size=n) - 1) % card)
        rows[:, j] = (murmur64_fixed(head[a:a + 12].T) % vs).astype(
            np.int64)

    # Bag lengths, then every word of every bag in line order.
    lens = np.stack([np.clip(np.rint(rng.lognormal(
        np.log(float(b["median"])), float(b["sigma"]), size=n)), 1,
        int(b["cap"])).astype(np.int64) for b in bags])        # [bags, n]
    ends = np.cumsum(lens, axis=0)
    per_line = ends[-1]
    total = int(per_line.sum())
    first = np.cumsum(per_line) - per_line     # a line's first word
    line = np.repeat(np.arange(n), per_line)
    pos = np.arange(total) - first[line]       # a word's place in its line
    bag = (pos[None, :] >= ends[:-1, line]).sum(axis=0)
    val = np.rint(1000.0 / lens[bag, line]).astype(np.int32)
    word = (rng.zipf(zipf_a, size=total) - 1) % words
    # A word's table row, hashed once a vocabulary entry.
    entry = np.empty((10, words), dtype=np.uint8)
    entry[0], entry[1] = ord("W"), ord("=")
    entry[2:] = _hex8(np.arange(words))
    word_row = (murmur64_fixed(entry.T) % vs).astype(np.int64)
    rows[line, n_ids + pos] = word_row[word]
    millis[line, n_ids + pos] = val

    tok = np.empty((_WORD_TOKEN, total), dtype=np.uint8)
    tok[:] = np.frombuffer(b" W=00000000:0.000", dtype=np.uint8)[:, None]
    tok[3:11] = _hex8(word)
    tok[12] = 48 + val // 1000
    tok[14] = 48 + (val // 100) % 10
    tok[15] = 48 + (val // 10) % 10
    tok[16] = 48 + val % 10

    # The text: a line is its head, its words and a newline. Written
    # as blocks of one width, "\n" + head, with a line's words between
    # its block and the next (two masked copies, no scattered write);
    # the text starts past the first block's newline and ends in one.
    block = np.empty((n, 1 + head.shape[0]), dtype=np.uint8)
    block[:, 0] = ord("\n")
    block[:, 1:] = head.T
    size = block.shape[1] + _WORD_TOKEN * per_line
    start = np.cumsum(size) - size
    edge = np.zeros(int(size.sum()) + 1, dtype=np.int8)
    edge[start] = 1
    edge[start + block.shape[1]] = -1
    in_block = np.cumsum(edge[:-1], dtype=np.int8).view(np.bool_)
    text = np.empty(len(edge), dtype=np.uint8)
    text[:-1][in_block] = block.ravel()
    text[:-1][~in_block] = tok.T.ravel()
    text[-1] = ord("\n")
    text = text[1:]     # a line now starts where its block did

    os.makedirs(out_dir, exist_ok=True)
    files, per_file = [], []
    bounds = np.linspace(0, n, n_files + 1).astype(int)
    at = np.append(start, len(text))        # where a line's text starts
    for i in range(n_files):
        path = os.path.join(out_dir, f"{prefix}-{i:03d}.libsvm")
        text[at[bounds[i]]:at[bounds[i + 1]]].tofile(path)
        files.append(path)
        per_file.append(int(bounds[i + 1] - bounds[i]))
    return Corpus(labels=labels, rows=rows, millis=millis,
                  fields=np.zeros(W, dtype=np.int32), files=files,
                  lines_per_file=per_file)


@contextlib.contextmanager
def in_place_of_generate():
    """For the block, ``benchmarks.corpus.generate`` is this module's:
    whoever makes a corpus through that name (drivers/train.py
    ``make_corpus``, control.py ``control_numbers``) gets lines with
    bags."""
    kept = corpus_mod.generate
    corpus_mod.generate = generate
    try:
        yield
    finally:
        corpus_mod.generate = kept
