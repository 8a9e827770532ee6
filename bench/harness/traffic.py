"""Packed training traffic, generated from a mix file and a seed.

The length distributions and the doc-pure-block packing are copies of
the program's data pipeline, kept here so that the yardstick does not
move when the program's generator changes.  A mix file
(``bench/traffic/<name>.json``) names the distribution, the row length,
the longest document, the rows each rank owns per step, and how many
distinct batch layouts one run cycles through.

Every seed gets the same set of layouts (drawn from the mix's
``layout_seed``) in its own order, with its own token ids: any run of
``layouts`` consecutive steps does the same work whatever the seed, and
no two rows of a run repeat.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

BLOCK = 128


def seed32(seed: int, salt: int = 0) -> int:
    """A non-negative 31-bit seed from any whole number (the driver's
    seeds exceed 32 signed bits)."""
    state = np.random.SeedSequence([int(seed) & (2 ** 64 - 1), salt])
    return int(state.generate_state(1, dtype=np.uint32)[0] & 0x7FFFFFFF)


# --------------------------------------------------------------- lengths
def pretrain_lengths(rng: np.random.Generator, n: int, max_len: int,
                     min_len: int = 128, alpha: float = 1.3,
                     upsample_threshold: int = 0,
                     upsample_drop: float = 0.7) -> np.ndarray:
    """Power-law lengths in [min_len, max_len]; optionally upsample long
    docs by dropping a fraction of docs below ``upsample_threshold``."""
    u = rng.random(n)
    lo, hi = float(min_len), float(max_len)
    a1 = 1.0 - alpha
    ls = ((lo ** a1) + u * ((hi ** a1) - (lo ** a1))) ** (1.0 / a1)
    ls = np.clip(ls, lo, hi).astype(np.int64)
    if upsample_threshold:
        keep = (ls >= upsample_threshold) | \
            (rng.random(n) > upsample_drop)
        ls = ls[keep]
    return ls


def prolong_lengths(rng: np.random.Generator, n: int,
                    max_len: int) -> np.ndarray:
    """60% power law up to min(8192, max_len), 40% log-uniform in
    [max(max_len/16, 256), max_len]."""
    n_long = int(n * 0.4)
    short = pretrain_lengths(rng, n - n_long, min(8192, max_len))
    lo, hi = np.log(max(max_len // 16, 256)), np.log(max_len)
    long_ = np.exp(rng.random(n_long) * (hi - lo) + lo).astype(np.int64)
    ls = np.concatenate([short, np.clip(long_, 256, max_len)])
    rng.shuffle(ls)
    return ls


def sample_lengths(name: str, rng: np.random.Generator, n: int,
                   max_len: int) -> np.ndarray:
    if name == "pretrain":
        return pretrain_lengths(rng, n, max_len,
                                upsample_threshold=max_len // 8)
    if name == "prolong":
        return prolong_lengths(rng, n, max_len)
    raise KeyError(f"unknown length distribution {name!r}")


# --------------------------------------------------------------- packing
def _aligned(n: int) -> int:
    return -(-n // BLOCK) * BLOCK


def pack_rows(doc_lengths: Sequence[int], seq_len: int,
              n_rows: int) -> List[List[int]]:
    """Greedy first-fit of documents into ``n_rows`` rows of ``seq_len``
    tokens, each document padded to the 128-token block.  A document
    that does not fit whole is cut at a block boundary and the rest
    continues as a new document in the next row with room; what is
    left when every row is full is dropped."""
    if seq_len % BLOCK:
        raise ValueError(f"seq_len {seq_len} is not a multiple of {BLOCK}")
    rows: List[List[int]] = [[] for _ in range(n_rows)]
    used = [0] * n_rows
    for l in doc_lengths:
        l = int(l)
        while l > 0:
            r = next((i for i in range(n_rows)
                      if seq_len - used[i] >= BLOCK), None)
            if r is None:
                return rows
            take = min(l, seq_len - used[r])
            rows[r].append(take)
            used[r] += _aligned(take)
            l -= take
    return rows


def layout(rows: List[List[int]], seq_len: int) -> Dict[str, np.ndarray]:
    """segment_ids (documents numbered from 1, 0 = padding) and
    in-document positions of packed rows."""
    seg = np.zeros((len(rows), seq_len), np.int32)
    pos = np.zeros((len(rows), seq_len), np.int32)
    doc = 1
    for r, lens in enumerate(rows):
        t = 0
        for l in lens:
            seg[r, t:t + l] = doc
            pos[r, t:t + l] = np.arange(l)
            doc += 1
            t += _aligned(l)
    return {"segment_ids": seg, "positions": pos}


def labels(tokens: np.ndarray, seg: np.ndarray) -> np.ndarray:
    """Next-token labels inside a document, -1 at its last token and on
    padding."""
    nxt = np.roll(tokens, -1, axis=-1)
    nseg = np.roll(seg, -1, axis=-1)
    return np.where((seg > 0) & (seg == nseg), nxt, -1).astype(np.int32)


class Traffic:
    """The batches of one run: ``mix`` is the parsed mix file, ``ranks``
    the cell's CAD ranks, ``vocab`` the configuration's vocabulary."""

    def __init__(self, mix: Dict, ranks: int, vocab: int, seed: int):
        self.mix = mix
        self.seq_len = int(mix["seq_len"])
        self.rows = int(mix["rows_per_rank"]) * ranks
        self.vocab = int(vocab)
        rng = np.random.default_rng(seed32(mix["layout_seed"], 1))
        self.layouts = [self._draw_layout(rng)
                        for _ in range(int(mix["layouts"]))]
        self.order = np.random.default_rng(seed32(seed, 2)).permutation(
            len(self.layouts))
        self.seed = seed

    def _draw_layout(self, rng) -> Dict[str, np.ndarray]:
        need = self.rows * self.seq_len
        lens: List[int] = []
        while sum(lens) < need * 1.2:
            lens.extend(sample_lengths(self.mix["distribution"], rng, 64,
                                       int(self.mix["max_doc_len"])))
        return layout(pack_rows(lens, self.seq_len, self.rows),
                      self.seq_len)

    def batch(self, i: int) -> Dict[str, np.ndarray]:
        """Step ``i``'s batch: host arrays with the fields of the
        program's pipeline (tokens, labels, segment_ids, positions)."""
        lay = self.layouts[self.order[i % len(self.order)]]
        rng = np.random.default_rng(seed32(self.seed, 1000 + i))
        seg = lay["segment_ids"]
        tokens = np.where(seg > 0, rng.integers(1, self.vocab, seg.shape),
                          0).astype(np.int32)
        return {"tokens": tokens, "labels": labels(tokens, seg),
                "segment_ids": seg.copy(), "positions": lay["positions"].copy()}

    def stream(self):
        i = 0
        while True:
            yield self.batch(i)
            i += 1
