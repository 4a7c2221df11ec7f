"""Operations and bytes from shapes, checked by hand at small sizes."""
from __future__ import annotations

from bench import costs, device

S = costs.Shapes(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, ff=16,
                 gated=True, vocab=32)


def test_gemv():
    c = costs.gemv(rows=3, r=5, c=7, biased=True)
    assert c.flops == 2 * 3 * 5 * 7
    assert c.bytes == 2 * (5 * 7 + 3 * 7 + 3 * 5 + 5)


def test_layer_matrices_and_program():
    # q 8x8, k 4x8, v 4x8, o 8x8, up 16x8, gate 16x8, down 8x16
    assert S.layer_params() == 64 + 32 + 32 + 64 + 128 + 128 + 128
    calls = costs.gemv_program(S, rows=4, head_rows=1)
    assert len(calls) == 7 * 2 + 1
    assert calls[-1].flops == 2 * 1 * 32 * 8


def test_paged_decode_rounds_pages():
    c = costs.paged_decode(S, lens=[5, 16], page=8, slots=3)
    kv = (8 + 16) * 1 * 4 * 2 * 2          # tokens x heads x Dh x B x (K,V)
    assert c.bytes == kv + 2 * 3 * 2 * 4 * 2
    assert c.flops == 4 * 2 * 4 * (5 + 16)


def test_model_flops():
    per_tok = 2 * (2 * S.layer_params() + 32 * 8)
    assert costs.model_flops_decode(S, [3]) == per_tok + 4 * 2 * 4 * 2 * 3
    # chunk of 2 tokens at start 1: keys 2 + 3 = 5
    assert costs.model_flops_chunk(S, 1, 2) == (
        2 * 2 * S.layer_params() * 2 + 4 * 2 * 4 * 2 * 5 + 2 * 32 * 8)


def test_ideal_picks_the_bound():
    peak = device.peaks("TPU v5 lite")
    t, bound = costs.ideal_s(costs.Cost(flops=1e12, bytes=1e6), peak)
    assert bound == "compute" and t == 1e12 / 197e12
    t, bound = costs.ideal_s(costs.Cost(flops=1.0, bytes=819e9), peak)
    assert bound == "memory" and t == 1.0
