"""Compile the serving path's Pallas kernels for TPU v5e, without a chip.

Every kernel `ServingEngine` reaches is lowered and compiled here by the
TPU compiler against a described (not attached) v5e at gpt2_medium's
published widths — 16 heads, head_dim 64, 16-token pages, bf16, vocab
50257 — and must come out as a Mosaic `tpu_custom_call`. Interpret-mode
tests cannot see what this catches: block shapes Mosaic refuses, vector
ops it cannot lower, values a kernel closes over instead of taking as
operands.

The topology is described inside a module fixture (never at import): only
one process may hold the TPU library, and the suite runs under several
xdist workers, each importing every test file. Keep these cases in this
one file so that a single worker owns the library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.lut import LutBank
from repro.kernels import ops

H = HKV = 16          # gpt2_medium heads (MHA)
D = 64                # head_dim
PAGE = 16             # tokens per KV page
B = 4                 # decode slots
N_PAGES = 16          # block-table width: max_len 256
P = 1 + B * N_PAGES   # pool pages incl. the trash page
BF16, I8, I32 = jnp.bfloat16, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Compiles for a described chip cannot be read back from the
    # persistent cache; keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _exp_table(sharding):
    t = LutBank.create(64).exp
    return jax.tree.map(lambda a: _sds(sharding, a.shape, a.dtype), t)


def _pools(s, kv, n_pages=N_PAGES):
    """(q, k_pages, v_pages, tables, lengths, k_scales, v_scales, window)
    shape stand-ins for one layer's paged decode at pool dtype `kv`."""
    pool_pages = 1 + B * n_pages
    dp = D // 2 if kv == "int4" else D
    pool_dtype = BF16 if kv == "bf16" else I8
    scale_dtype = {"bf16": None, "int8": jnp.float32, "int4": BF16}[kv]
    pool = _sds(s, (pool_pages, HKV, PAGE, dp), pool_dtype)
    scales = (None if scale_dtype is None
              else _sds(s, (pool_pages, HKV, 1, PAGE), scale_dtype))
    return (_sds(s, (B, H, D), BF16), pool, pool,
            _sds(s, (B, n_pages), I32), _sds(s, (B,), I32),
            scales, scales, _sds(s, (), I32))


PAGED_DECODE_CASES = {
    "bf16-exact": dict(kv="bf16"),
    "bf16-lut": dict(kv="bf16", lut=True),
    "bf16-kv-splits-4": dict(kv="bf16", kv_splits=4, n_pages=64),
    "int8-f32-scales": dict(kv="int8"),
    "int4-bf16-scales": dict(kv="int4"),
}


@pytest.mark.parametrize("case", PAGED_DECODE_CASES)
def test_paged_decode_compiles(one_chip, case):
    kw = PAGED_DECODE_CASES[case]
    q, kp, vp, tbl, lens, ksc, vsc, win = _pools(
        one_chip, kw["kv"], kw.get("n_pages", N_PAGES))
    table = _exp_table(one_chip) if kw.get("lut") else None

    def step(q, kp, vp, tbl, lens, ksc, vsc, win, table):
        return ops.pim_paged_attention(
            q, kp, vp, tbl, lens, ksc, vsc, exp_table=table, window=win,
            kv_splits=kw.get("kv_splits"), impl="pallas")

    assert "tpu_custom_call" in _compiled_text(
        step, q, kp, vp, tbl, lens, ksc, vsc, win, table)


@pytest.mark.parametrize("kv,lut", [("bf16", True), ("int8", False)])
def test_paged_prefill_compiles(one_chip, kv, lut):
    sq = 64
    _, kp, vp, _, lens, ksc, vsc, win = _pools(one_chip, kv)
    q = _sds(one_chip, (1, sq, H, D), BF16)
    tbl = _sds(one_chip, (1, N_PAGES), I32)
    start = lens = _sds(one_chip, (1,), I32)
    table = _exp_table(one_chip) if lut else None

    def chunk(q, kp, vp, tbl, lens, start, ksc, vsc, win, table):
        return ops.pim_paged_prefill_attention(
            q, kp, vp, tbl, lens, start, ksc, vsc, exp_table=table,
            window=win, impl="pallas")

    assert "tpu_custom_call" in _compiled_text(
        chunk, q, kp, vp, tbl, lens, start, ksc, vsc, win, table)


def test_dense_decode_with_window_operand_compiles(one_chip):
    s = one_chip
    q = _sds(s, (B, H, D), BF16)
    k = v = _sds(s, (B, HKV, 256, D), BF16)

    def step(q, k, v, lens, win):
        return ops.pim_decode_attention(q, k, v, lens, window=win,
                                        impl="pallas")

    assert "tpu_custom_call" in _compiled_text(
        step, q, k, v, _sds(s, (B,), I32), _sds(s, (), I32))


@pytest.mark.parametrize("c,r", [(1024, 4096), (4096, 1024), (1024, 50257)])
def test_gemv_float_compiles(one_chip, c, r):
    s = one_chip

    def linear(x, w, b):
        return ops.pim_linear(x, w, b, impl="pallas")

    assert "tpu_custom_call" in _compiled_text(
        linear, _sds(s, (B, c), BF16), _sds(s, (r, c), BF16),
        _sds(s, (r,), BF16))
