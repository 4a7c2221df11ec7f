"""The generator: same work for every seed, in another order."""
from __future__ import annotations

import numpy as np
import pytest

from bench import spec, traffic

CELLS = ["gpt2m.paper.poisson", "qwen2-1.5b.longdoc.closed16"]


def block(t, seed, vocab=1000):
    s = traffic.RequestStream(t, seed, vocab)
    return [s.next() for _ in range(t.get("block", traffic.DEFAULT_BLOCK))]


@pytest.mark.parametrize("name", CELLS)
def test_every_seed_offers_the_same_schedule(name):
    t = spec.load_cell(name).traffic
    a, b = block(t, 3), block(t, 2**33 + 7)
    assert [(len(p), o, d) for p, o, d in a] == [(len(p), o, d)
                                                for p, o, d in b]
    assert any((pa != pb).any() for (pa, _, _), (pb, _, _) in zip(a, b))
    e = t["engine"]
    for p, o, _ in a:
        assert len(p) + o <= e["max_len"]
        assert 1 <= o


@pytest.mark.parametrize("name", CELLS)
def test_each_block_holds_the_whole_multiset(name):
    t = spec.load_cell(name).traffic
    k = t.get("block", traffic.DEFAULT_BLOCK)
    s = traffic.RequestStream(t, 1, 1000)
    items = [s.next() for _ in range(2 * k)]
    for blk in (items[:k], items[k:]):
        assert sorted(len(p) for p, _, _ in blk) == list(
            traffic.block_values(t["prompt_len"], k))
        assert sorted(o for _, o, _ in blk) == list(
            traffic.block_values(t["output_len"], k))


def test_open_loop_gaps_average_to_the_rate():
    t = spec.load_cell("gpt2m.paper.poisson").traffic
    items = block(t, 5)
    due = np.array([d for _, _, d in items])
    assert due[0] == -t.get("warm_s", 0.0)
    gaps = np.diff(np.append(due, due[-1]))
    k = len(items)
    assert np.isclose(traffic._gaps(t["rate_rps"], k).mean(),
                      1 / t["rate_rps"])
    assert (gaps >= 0).all()


def test_same_seed_same_requests():
    t = spec.load_cell("gpt2m.paper.poisson").traffic
    a, b = block(t, 9), block(t, 9)
    assert all((pa == pb).all() and oa == ob and da == db
               for (pa, oa, da), (pb, ob, db) in zip(a, b))


def test_chunk_shapes():
    assert spec.chunk_shapes(spec.load_cell("gpt2m.paper.poisson")) == [32, 64]
    assert spec.chunk_shapes(
        spec.load_cell("qwen2-1.5b.longdoc.closed16")) == [512]
