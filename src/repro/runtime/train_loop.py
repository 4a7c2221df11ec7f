"""Fault-tolerant training loop: jit'd step, checkpoint/restart, preemption
save, straggler watch, metrics log. Designed so the same loop runs on 1
CPU device (tests) and on the production mesh (launch/train.py).
"""
from __future__ import annotations

import dataclasses
import json
import signal
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.core.salpim import SalPimEngine
from repro.data import tokens as data_lib
from repro.distributed import sharding as shard_lib
from repro.distributed.api import use_mesh
from repro.models import api as model_api
from repro.models.config import ModelConfig
from repro.runtime import checkpoint as ckpt_lib
from repro.runtime import optimizer as opt_lib


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50
    keep: int = 3
    log_every: int = 10
    n_micro: int = 1
    straggler_zscore: float = 4.0
    metrics_path: Optional[str] = None
    async_ckpt: bool = True


def make_train_step(model_cfg: ModelConfig, engine: SalPimEngine,
                    opt_cfg: opt_lib.AdamWConfig,
                    *, n_micro: int = 1) -> Callable:
    """Pure (params, opt_state, batch) -> (params, opt_state, metrics).

    The step runs the jnp path (impl="reference") whatever `engine`
    resolved to: the Pallas kernels carry no gradient rule, and XLA
    cannot partition them over a training mesh."""
    engine = dataclasses.replace(
        engine, config=dataclasses.replace(engine.config, impl="reference"))

    def loss_fn(params, batch):
        return model_api.loss_fn(params, batch, model_cfg, engine)

    def step(params, opt_state, batch):
        loss, grads, metrics = opt_lib.accumulate_grads(
            loss_fn, params, batch, n_micro)
        params, opt_state, opt_metrics = opt_lib.adamw_update(
            opt_cfg, params, grads, opt_state)
        metrics = {**metrics, **opt_metrics, "loss": loss}
        return params, opt_state, metrics

    return step


def jit_train_step(step_fn: Callable, mesh, params_shape, batch_shape,
                   *, fsdp: bool = False):
    """Wrap with explicit in/out shardings on `mesh` (None -> plain jit)."""
    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0, 1))
    pshard = shard_lib.param_shardings(params_shape, mesh, fsdp=fsdp)
    oshard = opt_lib.OptState(
        step=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        mu=pshard, nu=pshard)
    bshard = shard_lib.to_shardings(
        shard_lib.batch_pspecs(batch_shape, mesh), mesh)
    return jax.jit(
        step_fn,
        in_shardings=(pshard, oshard, bshard),
        out_shardings=(pshard, oshard, None),
        donate_argnums=(0, 1),
    )


class StragglerWatch:
    """Per-step wall-time EMA + z-score alarm (the mitigation at scale is
    rebalancing/evicting the slow host; here we detect and log)."""

    def __init__(self, zscore: float = 4.0, warmup: int = 5):
        self.z = zscore
        self.warmup = warmup
        self.n = 0
        self.mean = 0.0
        self.m2 = 1e-12

    def observe(self, dt: float) -> Optional[str]:
        self.n += 1
        delta = dt - self.mean
        self.mean += delta / self.n
        self.m2 += delta * (dt - self.mean)
        if self.n <= self.warmup:
            return None
        std = max((self.m2 / (self.n - 1)) ** 0.5, 1e-9)
        if (dt - self.mean) / std > self.z:
            return (f"straggler: step took {dt*1e3:.1f} ms "
                    f"(mean {self.mean*1e3:.1f} ms, z>{self.z})")
        return None


def run_training(
    model_cfg: ModelConfig,
    train_cfg: TrainConfig,
    opt_cfg: opt_lib.AdamWConfig,
    data_cfg: data_lib.DataConfig,
    *,
    engine: Optional[SalPimEngine] = None,
    mesh=None,
    fsdp: bool = False,
    seed: int = 0,
    hooks: Optional[dict] = None,
) -> dict:
    """Returns final {params, opt_state, data_state, history}."""
    engine = engine or SalPimEngine.create(model_cfg.salpim)
    hooks = hooks or {}
    key = jax.random.PRNGKey(seed)

    with use_mesh(mesh):
        params = model_api.init_params(key, model_cfg)
        if mesh is not None:
            pshard = shard_lib.param_shardings(params, mesh, fsdp=fsdp)
            params = jax.tree.map(jax.device_put, params, pshard)
        opt_state = opt_lib.init_opt_state(params)
        data_state = data_lib.DataState()

        # --- resume -------------------------------------------------------
        start_step = 0
        latest = ckpt_lib.latest_step(train_cfg.ckpt_dir)
        if latest is not None:
            shardings = None
            if mesh is not None:
                shardings = {
                    "params": shard_lib.param_shardings(params, mesh, fsdp=fsdp),
                    "opt": opt_lib.OptState(
                        step=None,
                        mu=shard_lib.param_shardings(params, mesh, fsdp=fsdp),
                        nu=shard_lib.param_shardings(params, mesh, fsdp=fsdp)),
                }
            tree, manifest = ckpt_lib.restore(
                train_cfg.ckpt_dir,
                {"params": params, "opt": opt_state},
                shardings=shardings)
            params, opt_state = tree["params"], tree["opt"]
            start_step = manifest["extra"].get("next_step", manifest["step"])
            data_state.step = manifest["extra"].get("data_step", start_step)

        step_fn = make_train_step(model_cfg, engine, opt_cfg,
                                  n_micro=train_cfg.n_micro)
        jitted = jit_train_step(
            step_fn, mesh, jax.eval_shape(lambda: params),
            jax.eval_shape(lambda: data_lib.batch_at(data_cfg, 0)),
            fsdp=fsdp)

        # --- preemption handling -------------------------------------------
        preempted = {"flag": False}

        def on_term(signum, frame):
            preempted["flag"] = True

        prev_handler = signal.signal(signal.SIGTERM, on_term)

        watch = StragglerWatch(train_cfg.straggler_zscore)
        history = []
        mpath = train_cfg.metrics_path
        mfile = open(mpath, "a") if mpath else None

        def save(step, blocking=False):
            extra = {"next_step": step, "data_step": data_state.step}
            tree = {"params": params, "opt": opt_state}
            if train_cfg.async_ckpt and not blocking:
                ckpt_lib.save_async(train_cfg.ckpt_dir, step, tree,
                                    extra=extra, keep=train_cfg.keep)
            else:
                ckpt_lib.save(train_cfg.ckpt_dir, step, tree, extra=extra,
                              keep=train_cfg.keep)

        try:
            for step in range(start_step, train_cfg.steps):
                t0 = time.perf_counter()
                batch = data_lib.batch_at(data_cfg, data_state.step)
                data_state.step += 1
                params, opt_state, metrics = jitted(params, opt_state, batch)
                jax.block_until_ready(metrics["loss"])
                dt = time.perf_counter() - t0

                warn = watch.observe(dt)
                if warn and "on_straggler" in hooks:
                    hooks["on_straggler"](step, warn)

                if step % train_cfg.log_every == 0 or step == train_cfg.steps - 1:
                    rec = {k: float(np.asarray(v)) for k, v in metrics.items()}
                    rec.update(step=step, sec_per_step=dt)
                    history.append(rec)
                    if mfile:
                        mfile.write(json.dumps(rec) + "\n")
                        mfile.flush()
                    if "on_log" in hooks:
                        hooks["on_log"](rec)

                if (step + 1) % train_cfg.ckpt_every == 0:
                    save(step + 1)
                if preempted["flag"]:
                    save(step + 1, blocking=True)
                    break
        except Exception:
            # Crash-path checkpoint: restartable at the last good step.
            save_step = int(np.asarray(opt_state.step))
            try:
                save(save_step, blocking=True)
            finally:
                pass
            raise
        finally:
            signal.signal(signal.SIGTERM, prev_handler)
            if mfile:
                mfile.close()

        save(min(train_cfg.steps, max(start_step, train_cfg.steps)),
             blocking=True)
    return {"params": params, "opt_state": opt_state,
            "data_state": data_state, "history": history}
