"""Runs a family's plain float32 reference over served requests.

Each request is its prompt followed by the tokens the timed path served
(teacher forcing: the reference reads the served tokens, not its own).
The reference goes layer by layer over all sampled requests at once,
upcasting one layer's weights at a time, so a float32 copy of the whole
model never exists beside the served one. Only the rows at which a token
was served reach the LM head.

`quant="int8"` (or "fp8") is the control: the same reference with every
matrix (projections, MLP, LM head) rounded to int8 (float8 e4m3) per
output row, the precision below the served bfloat16 that a change could
be tempted to serve in.

Two numbers are read against the reference's logits: how far each
served token lies below the reference's best (`served_gaps`), and how
far the program's own logits, kept as the window served them, lie from
the reference's (`logit_errors`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MATRICES = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down", "lm_head")


def int8_rows(w):
    """w rounded to int8 with one symmetric scale per output row."""
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-1, keepdims=True), 1e-12) / 127
    return jnp.round(w / s) * s


def fp8_rows(w):
    """w rounded to float8 e4m3, each output row scaled to its range."""
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-1, keepdims=True), 1e-12) / 448
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


ROUNDERS = {"int8": int8_rows, "fp8": fp8_rows}


def _quantize(tree, quant):
    if quant is None:
        return tree
    rnd = ROUNDERS[quant]
    return {k: (_quantize(v, quant) if isinstance(v, dict)
                else rnd(v) if k in MATRICES else v)
            for k, v in tree.items()}


class Reference:
    def __init__(self, fam, c: dict, params: dict, q_block: int,
                 quant: str | None = None):
        self.fam, self.c, self.p = fam, c, params
        self.q_block, self.quant = q_block, quant
        self.n_layers = jax.tree.leaves(params["blocks"])[0].shape[0]
        self._embed = jax.jit(jax.vmap(lambda p, t: fam.embed(p, t, c),
                                       in_axes=(None, 0)))
        self._layer = jax.jit(jax.vmap(
            lambda lp, x: fam.layer(_quantize(lp, quant), x, c, q_block),
            in_axes=(None, 0)))
        self._head = jax.jit(
            lambda p, x: fam.head(_quantize(p, quant), x, c))

    def logits(self, seqs: list[np.ndarray], rows: list[np.ndarray]):
        """seqs: token ids per request; rows: positions per request whose
        next-token logits are wanted. Returns float32 logits
        (sum of len(rows), vocab) on the device, rows in order."""
        n = max(len(s) for s in seqs)
        S = -(-n // self.q_block) * self.q_block
        toks = np.zeros((len(seqs), S), np.int32)
        for i, s in enumerate(seqs):
            toks[i, :len(s)] = s     # causal: padding at the end is unseen
        with jax.default_matmul_precision("highest"):
            x = self._embed(self.p, jnp.asarray(toks))
            blocks = self.p["blocks"]
            for li in range(self.n_layers):
                lp = jax.tree.map(lambda a, li=li: a[li], blocks)
                x = self._layer(lp, x)
            idx = np.concatenate([np.stack([np.full(len(r), i), r], 1)
                                  for i, r in enumerate(rows)])
            sel = x[idx[:, 0], idx[:, 1]]
            del x
            head = {k: v for k, v in self.p.items() if k != "blocks"}
            return self._head(head, sel)


@jax.jit
def served_gaps(ref_logits, tokens):
    """How far each served token's reference logit lies below the
    reference's best at that row, in units of that row's logit std."""
    best = ref_logits.max(-1)
    at = jnp.take_along_axis(ref_logits, tokens[:, None], -1)[:, 0]
    return (best - at) / ref_logits.std(-1)


@jax.jit
def control_gaps(ref_logits, ctrl_logits):
    """The same gap for the token the control puts first."""
    return served_gaps(ref_logits, jnp.argmax(ctrl_logits, -1))


@jax.jit
def logit_errors(ref_logits, other):
    """Per row, the root mean square of other - reference over the
    vocabulary, in units of that row's reference logit std."""
    d = other.astype(jnp.float32) - ref_logits
    return jnp.sqrt(jnp.mean(d * d, -1)) / ref_logits.std(-1)
