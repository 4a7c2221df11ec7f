"""Distribution layer: sharding rules (pure), and 8-fake-device subprocess
tests — sharded==unsharded train step, pipeline parallelism, compressed
psum, sequence-parallel softmax merge (the C-ALU analogue)."""
from __future__ import annotations

import pytest

from repro.configs import cells, LONG_CONTEXT_SKIP_REASON


def test_cell_listing_counts():
    live = cells()
    everything = cells(include_skipped=True)
    assert len(everything) == 40
    assert len(live) == 34
    assert len(LONG_CONTEXT_SKIP_REASON) >= 6


@pytest.mark.multidevice
def test_param_pspec_divisibility(subproc):
    """Every rule-produced spec must evenly divide its tensor on the
    production mesh — for every arch (the 12-head qwen2 case etc.)."""
    code = """
import jax
from repro.configs import ARCHS, get_config
from repro.models import api as model_api
from repro.distributed import sharding as sh
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
for arch in ARCHS:
    cfg = get_config(arch)
    params = jax.eval_shape(lambda c=cfg: model_api.init_params(jax.random.PRNGKey(0), c))
    for fsdp in (False, True):
        specs = sh.param_pspecs(params, mesh, fsdp=fsdp)
        flat_s = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        flat_p = jax.tree_util.tree_leaves(params)
        for spec, leaf in zip(flat_s, flat_p):
            for dim, ax in zip(leaf.shape, tuple(spec)):
                if ax is None: continue
                names = (ax,) if isinstance(ax, str) else ax
                ext = 1
                for n in names: ext *= mesh.shape[n]
                assert dim % ext == 0, (arch, spec, leaf.shape)
print("ok")
"""
    assert "ok" in subproc(code, n_devices=8)


@pytest.mark.multidevice
def test_sharded_train_step_matches_unsharded(subproc):
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.core.salpim import SalPimEngine, SalPimConfig
from repro.data import tokens as D
from repro.models import api
from repro.runtime import optimizer as opt
from repro.runtime.train_loop import make_train_step, jit_train_step
from repro.distributed.api import use_mesh

cfg = get_config("qwen2_1_5b", smoke=True)
engine = SalPimEngine.create(SalPimConfig())
ocfg = opt.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
params = api.init_params(jax.random.PRNGKey(0), cfg)
state = opt.init_opt_state(params)
dcfg = D.DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8)
batch = D.batch_at(dcfg, 0)
step = make_train_step(cfg, engine, ocfg)

# unsharded reference
p1, s1, m1 = jax.jit(step)(params, state, batch)

# sharded on a (2,4) mesh
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with use_mesh(mesh), mesh:
    jitted = jit_train_step(step, mesh,
                            jax.eval_shape(lambda: params),
                            jax.eval_shape(lambda: batch), fsdp=True)
    p2, s2, m2 = jitted(params, opt.init_opt_state(params), batch)

assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, (m1["loss"], m2["loss"])
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=1e-3, atol=1e-4)
print("ok", float(m1["loss"]))
"""
    assert "ok" in subproc(code, n_devices=8, timeout=900)


@pytest.mark.multidevice
def test_sharded_decode_matches_unsharded(subproc):
    code = """
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_config
from repro.core.salpim import SalPimEngine, SalPimConfig
from repro.models import api
from repro.distributed import sharding as sh
from repro.distributed.api import use_mesh

cfg = dataclasses.replace(get_config("qwen2_1_5b", smoke=True), decode_uniform=True)
engine = SalPimEngine.create(SalPimConfig())
params = api.init_params(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 12), 0, cfg.vocab)
logits, cache = api.prefill(params, {"tokens": toks}, cfg, engine, max_len=16)
tok = jnp.argmax(logits, -1).astype(jnp.int32)
l1, c1 = jax.jit(lambda p, t, c: api.decode_step(p, t, c, cfg, engine))(params, tok, cache)

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with use_mesh(mesh), mesh:
    pshard = sh.param_shardings(jax.eval_shape(lambda: params), mesh)
    cshard = sh.to_shardings(sh.cache_pspecs(jax.eval_shape(lambda: cache), mesh), mesh)
    fn = jax.jit(lambda p, t, c: api.decode_step(p, t, c, cfg, engine),
                 in_shardings=(pshard, None, cshard), out_shardings=(None, cshard))
    l2, c2 = fn(params, tok, cache)
np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=2e-3, atol=2e-3)
np.testing.assert_allclose(np.asarray(c1.k), np.asarray(c2.k), rtol=1e-5, atol=1e-5)
print("ok")
"""
    assert "ok" in subproc(code, n_devices=8, timeout=900)


@pytest.mark.multidevice
def test_pipeline_forward_equals_sequential(subproc):
    code = """
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.pipeline import make_pipelined_fn

mesh = jax.make_mesh((4,), ("pod",),
                     axis_types=(jax.sharding.AxisType.Auto,))
P_STAGES, B, D = 4, 8, 16
key = jax.random.PRNGKey(0)
stage_params = jax.random.normal(key, (P_STAGES, D, D)) * 0.3

def stage_fn(w, x):
    return jnp.tanh(x @ w)

x = jax.random.normal(jax.random.PRNGKey(1), (B, D))
# sequential reference
ref = x
for i in range(P_STAGES):
    ref = stage_fn(stage_params[i], ref)

fn = make_pipelined_fn(stage_fn, mesh, "pod", n_micro=4)
out = fn(stage_params, x)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)
print("ok")
"""
    assert "ok" in subproc(code, n_devices=4, timeout=600)


@pytest.mark.multidevice
def test_compressed_psum_and_softmax_merge(subproc):
    code = """
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.distributed.collectives import compressed_psum, merge_partial_softmax

mesh = jax.make_mesh((8,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

@partial(shard_map, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
def reduce_fn(gs):
    mean, ef = compressed_psum(gs[0], "data")
    return (mean + 0 * ef.sum())[None]

got = reduce_fn(g)
want = jnp.mean(g, axis=0)
np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), rtol=0.05, atol=0.02)

# C-ALU-style partial softmax merge across sequence shards
S, D = 64, 8
scores = jax.random.normal(jax.random.PRNGKey(1), (S,)) * 3
v = jax.random.normal(jax.random.PRNGKey(2), (S, D))
want_sm = jax.nn.softmax(scores) @ v

@partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=P(None))
def sharded_softmax_attend(sc, vv):
    m = jnp.max(sc, keepdims=True)[None]               # (1,1)
    e = jnp.exp(sc - m[0])
    l = jnp.sum(e, keepdims=True)[None]
    acc = (e @ vv)[None]
    return merge_partial_softmax(m, l, acc, "data")

got_sm = sharded_softmax_attend(scores, v)
np.testing.assert_allclose(np.asarray(got_sm[0]), np.asarray(want_sm),
                           rtol=1e-4, atol=1e-4)
print("ok")
"""
    assert "ok" in subproc(code, n_devices=8, timeout=600)


@pytest.mark.multidevice
def test_long_context_2axis_seq_sharded_decode(subproc):
    """Cell D rule: B=1 long decode shards the KV seq over BOTH axes;
    results must match the unsharded oracle (C-ALU merge correctness)."""
    code = """
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.core.salpim import SalPimEngine, SalPimConfig
from repro.models import api
from repro.distributed import sharding as sh
from repro.distributed.api import use_mesh

cfg = dataclasses.replace(get_config("h2o_danube3_4b", smoke=True),
                          decode_uniform=True, sliding_window=24)
engine = SalPimEngine.create(SalPimConfig())
params = api.init_params(jax.random.PRNGKey(0), cfg)
toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 0, cfg.vocab)
logits, cache = api.prefill(params, {"tokens": toks}, cfg, engine, max_len=64)
tok = jnp.argmax(logits, -1).astype(jnp.int32)
l1, c1 = jax.jit(lambda p, t, c: api.decode_step(p, t, c, cfg, engine))(params, tok, cache)

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with use_mesh(mesh), mesh:
    pshard = sh.param_shardings(jax.eval_shape(lambda: params), mesh)
    cspec = sh.cache_pspecs(jax.eval_shape(lambda: cache), mesh, seq_shard=True)
    # B=1: the KV seq dim must carry both axes (64 % 8 == 0)
    assert tuple(cspec.k)[3] == ("data", "model"), cspec.k
    cshard = sh.to_shardings(cspec, mesh)
    fn = jax.jit(lambda p, t, c: api.decode_step(p, t, c, cfg, engine),
                 in_shardings=(pshard, None, cshard), out_shardings=(None, cshard))
    l2, c2 = fn(params, tok, cache)
np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=2e-3, atol=2e-3)
print("ok")
"""
    assert "ok" in subproc(code, n_devices=8, timeout=900)
