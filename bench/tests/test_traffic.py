"""The traffic generator: seeded, valid, bounded."""
import json
from pathlib import Path

import numpy as np
import pytest

from harness import traffic as T

MIXES = sorted((Path(__file__).resolve().parents[1] / "traffic").glob("*.json"))
BIG_SEED = 2 ** 31 + 987654321


def mix(path, **over):
    m = json.loads(path.read_text())
    m.update(over)
    return m


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_batches_other_seed_other_tokens(path):
    m = mix(path, layouts=3)
    a, b = T.Traffic(m, 1, 1000, BIG_SEED), T.Traffic(m, 1, 1000, BIG_SEED)
    c = T.Traffic(m, 1, 1000, BIG_SEED + 1)
    for i in range(3):
        for k in ("tokens", "labels", "segment_ids", "positions"):
            np.testing.assert_array_equal(a.batch(i)[k], b.batch(i)[k])
    assert any(not np.array_equal(a.batch(i)["tokens"], c.batch(i)["tokens"])
               for i in range(3))
    # rows never repeat inside a run
    assert not np.array_equal(a.batch(0)["tokens"], a.batch(3)["tokens"])


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_every_seed_gets_the_same_layouts_in_its_own_order(path):
    m = mix(path, layouts=4)
    a, b = T.Traffic(m, 1, 1000, 1), T.Traffic(m, 1, 1000, 2)
    key = lambda t: sorted(l["segment_ids"].tobytes() for l in t.layouts)
    assert key(a) == key(b)


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
@pytest.mark.parametrize("ranks", [1, 4])
def test_layout_is_doc_pure_and_in_bounds(path, ranks):
    m = mix(path, layouts=4)
    t = T.Traffic(m, ranks, 1000, 5)
    b = t.batch(0)
    seg, pos, lab, tok = (b["segment_ids"], b["positions"], b["labels"],
                          b["tokens"])
    assert seg.shape == (m["rows_per_rank"] * ranks, m["seq_len"])
    blocks = seg.reshape(seg.shape[0], -1, T.BLOCK)
    for row in blocks:
        for blk in row:
            docs = set(blk[blk > 0].tolist())
            assert len(docs) <= 1            # one document per 128 block
    docs, counts = np.unique(seg[seg > 0], return_counts=True)
    assert counts.max() <= m["max_doc_len"]
    for d in docs:
        r, i = np.nonzero(seg == d)
        assert len(set(r.tolist())) == 1     # no document spans rows
        np.testing.assert_array_equal(pos[r, i], np.arange(len(i)))
    assert ((tok > 0) == (seg > 0)).all() and tok.max() < 1000
    # labels: the next token inside a document, -1 at its end and padding
    nxt = np.roll(tok, -1, axis=1)
    same = (seg > 0) & (np.roll(seg, -1, axis=1) == seg)
    np.testing.assert_array_equal(lab, np.where(same, nxt, -1))
    assert (seg > 0).mean() > 0.8            # packed, not padding


def test_lengths_stay_in_bounds():
    rng = np.random.default_rng(0)
    for name in ("pretrain", "prolong"):
        ls = T.sample_lengths(name, rng, 4096, 8192)
        assert ls.min() >= 128 and ls.max() <= 8192


def test_big_seeds_are_taken():
    assert 0 <= T.seed32(2 ** 40 + 3) < 2 ** 31
    assert T.seed32(2 ** 40 + 3) != T.seed32(3)
