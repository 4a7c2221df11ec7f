"""Training runtime: optimizer math, schedules, grad accumulation,
loss-goes-down smoke, straggler watch."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.data import tokens as data_lib
from repro.kernels import ops
from repro.models import api
from repro.runtime import optimizer as opt
from repro.runtime.train_loop import StragglerWatch, make_train_step

ENGINE = SalPimEngine.create(SalPimConfig())


def test_adamw_matches_naive_reference():
    cfg = opt.AdamWConfig(lr=1e-2, b1=0.9, b2=0.99, eps=1e-8,
                          weight_decay=0.0, clip_norm=None,
                          warmup_steps=0, total_steps=10**9, min_lr_ratio=1.0)
    p = {"w": jnp.array([1.0, -2.0, 3.0])}
    g = {"w": jnp.array([0.1, 0.2, -0.3])}
    st = opt.init_opt_state(p)
    newp, st2, _ = opt.adamw_update(cfg, p, g, st)
    # naive reference
    m = 0.1 * np.asarray(g["w"])
    v = 0.01 * np.asarray(g["w"]) ** 2
    mh = m / (1 - 0.9)
    vh = v / (1 - 0.99)
    want = np.asarray(p["w"]) - 1e-2 * mh / (np.sqrt(vh) + 1e-8)
    np.testing.assert_allclose(np.asarray(newp["w"]), want, rtol=1e-6)


def test_lr_schedule_shape():
    cfg = opt.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
    lrs = [float(opt.lr_at(cfg, jnp.asarray(s))) for s in range(0, 101, 5)]
    assert lrs[0] == 0.0
    assert abs(max(lrs) - 1.0) < 0.15           # peak near end of warmup
    assert abs(lrs[-1] - 0.1) < 0.02            # decays to min ratio
    assert all(b <= a + 1e-6 for a, b in zip(lrs[2:], lrs[3:]))  # monotone after peak


def test_clip_by_global_norm():
    g = {"a": jnp.ones((4,)) * 3.0, "b": jnp.ones((3,)) * 4.0}
    clipped, norm = opt.clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx((4 * 9 + 3 * 16) ** 0.5)
    new_norm = opt.global_norm(clipped)
    assert float(new_norm) == pytest.approx(1.0, rel=1e-5)


def test_grad_accumulation_equivalence():
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    dcfg = data_lib.DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=8)
    batch = {k: jnp.asarray(v) for k, v in data_lib.batch_at(dcfg, 0).items()}

    def loss_fn(p, b):
        return api.loss_fn(p, b, cfg, ENGINE)

    l1, g1, _ = opt.accumulate_grads(loss_fn, params, batch, 1)
    l4, g4, _ = opt.accumulate_grads(loss_fn, params, batch, 4)
    assert float(l1) == pytest.approx(float(l4), rel=2e-3)
    flat1, flat4 = jax.tree.leaves(g1), jax.tree.leaves(g4)
    for a, b in zip(flat1, flat4):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-4)


def test_loss_decreases_on_tiny_model():
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    ocfg = opt.AdamWConfig(lr=3e-3, warmup_steps=2, total_steps=40,
                           weight_decay=0.0)
    step = jax.jit(make_train_step(cfg, ENGINE, ocfg))
    state = opt.init_opt_state(params)
    dcfg = data_lib.DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8,
                               seed=7)
    losses = []
    for i in range(30):
        batch = data_lib.batch_at(dcfg, 0)   # overfit one batch
        params, state, metrics = step(params, state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


def test_train_step_differentiates_kernel_engine(monkeypatch):
    """A default-config engine on a TPU resolves to the Pallas kernels,
    which have no gradient rule; the train step must still run, on the
    jnp path. Interpret mode stands in for the TPU backend here."""
    monkeypatch.setattr(ops, "default_impl", lambda: "interpret")
    engine = SalPimEngine.create(SalPimConfig())
    assert engine.config.impl == "interpret"
    cfg = get_config("gpt2_medium", smoke=True)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    ocfg = opt.AdamWConfig()
    batch = data_lib.batch_at(data_lib.DataConfig(
        vocab=cfg.vocab, seq_len=16, global_batch=2), 0)
    state = opt.init_opt_state(params)
    got = jax.jit(make_train_step(cfg, engine, ocfg))(params, state, batch)
    want = jax.jit(make_train_step(cfg, ENGINE, ocfg))(params, state, batch)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_straggler_watch_flags_outlier():
    w = StragglerWatch(zscore=3.0, warmup=5)
    warn = None
    for _ in range(20):
        warn = w.observe(0.10 + np.random.RandomState(0).rand() * 0.001)
    assert warn is None
    assert w.observe(10.0) is not None
