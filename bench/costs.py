"""Operations and bytes that each kernel call needs, from its shapes.

A copy kept with the benchmark (the program's own cost model may change
under a later PR). Every function counts what the call is asked to do,
at the served dtype: bytes read and written from HBM at least once, and
multiply-adds as two operations. `ideal_s` is the least time the chip
could take: the larger of operations over peak and bytes over peak
bandwidth, with the bound that sets it.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shapes:
    """The widths the serving programs run at."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    gated: bool
    vocab: int
    itemsize: int = 2            # bf16 weights, activations and KV

    @classmethod
    def of(cls, cfg) -> "Shapes":
        return cls(cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                   cfg.head_dim, cfg.d_ff, cfg.gated_mlp, cfg.vocab)

    def layer_matrices(self) -> list[tuple[int, int, bool]]:
        """(out rows R, in cols C, biased) of every GEMV in one block."""
        nq = self.heads * self.head_dim
        nkv = self.kv_heads * self.head_dim
        m = [(nq, self.d, True), (nkv, self.d, True), (nkv, self.d, True),
             (self.d, nq, False), (self.ff, self.d, False)]
        if self.gated:
            m.append((self.ff, self.d, False))
        m.append((self.d, self.ff, False))
        return m

    def layer_params(self) -> int:
        return sum(r * c for r, c, _ in self.layer_matrices())


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0

    def __iadd__(self, o: "Cost") -> "Cost":
        self.flops += o.flops
        self.bytes += o.bytes
        return self


def ideal_s(c: Cost, peak: dict) -> tuple[float, str]:
    t_f = c.flops / peak["bf16_flops"]
    t_b = c.bytes / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")


def gemv(rows: int, r: int, c: int, biased: bool, itemsize: int = 2) -> Cost:
    """(rows, c) @ (r, c)^T (+ bias): weights, input, output once."""
    nbytes = itemsize * (r * c + rows * c + rows * r + (r if biased else 0))
    return Cost(2.0 * rows * r * c, nbytes)


def gemv_program(s: Shapes, rows: int, head_rows: int) -> list[Cost]:
    """Every GEMV call of one decode (rows = slots, head_rows = slots) or
    prefill-chunk (rows = chunk tokens, head_rows = 1) program."""
    calls = [gemv(rows, r, c, b, s.itemsize)
             for r, c, b in s.layer_matrices()] * s.layers
    calls.append(gemv(head_rows, s.vocab, s.d, False, s.itemsize))
    return calls


def paged_decode(s: Shapes, lens: list[int], page: int, slots: int) -> Cost:
    """One layer's paged decode attention over `slots` rows, of which the
    live ones hold KV lengths `lens` (after this step's append). Bytes:
    each live row's pages, page-rounded, K and V for every KV head, plus
    q in and out for every row; operations: QK and PV over the live
    tokens."""
    vec = s.head_dim * s.itemsize
    kv = sum(-(-n // page) * page for n in lens) * s.kv_heads * vec * 2
    qo = 2 * slots * s.heads * vec
    flops = 4.0 * s.heads * s.head_dim * sum(lens)
    return Cost(flops, kv + qo)


def model_flops_decode(s: Shapes, lens: list[int]) -> float:
    """Model operations of one decode step's live rows: every matrix
    (LM head included) per token, plus attention over its context."""
    per_tok = 2.0 * (s.layers * s.layer_params() + s.vocab * s.d)
    attn = 4.0 * s.heads * s.head_dim * s.layers * sum(lens)
    return per_tok * len(lens) + attn


def model_flops_chunk(s: Shapes, start: int, n: int) -> float:
    """Model operations of one prefill chunk of n prompt tokens at
    positions start..start+n-1: every block matrix per token, causal
    attention, and the LM head once (the chunk's last position)."""
    keys = n * start + n * (n + 1) / 2
    return (2.0 * s.layers * s.layer_params() * n
            + 4.0 * s.heads * s.head_dim * s.layers * keys
            + 2.0 * s.vocab * s.d)
