#!/usr/bin/env python
"""Quickstart: train a reduced GPT-2 with the SAL-PIM LUT engine, then
generate text — the paper's summarization+generation flow in ~1 minute.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.data import tokens as data_lib
from repro.runtime import optimizer as opt
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.train_loop import TrainConfig, run_training
from repro.serving.engine import GenConfig, generate


def main():
    enable_compile_cache()
    cfg = get_config("gpt2-medium", smoke=True)
    engine = SalPimEngine.create(SalPimConfig(nonlinear_mode="lut"))
    print(f"model: {cfg.name}  params={cfg.param_count():,}  "
          f"nonlinearities=LUT({engine.config.lut_sections} sections)")

    result = run_training(
        cfg,
        TrainConfig(steps=30, ckpt_dir="/tmp/quickstart_ckpt", ckpt_every=15,
                    log_every=10),
        opt.AdamWConfig(lr=3e-3, warmup_steps=3, total_steps=30),
        data_lib.data_config_for_model(cfg, seq_len=64, global_batch=8),
        engine=engine,
        hooks={"on_log": lambda r: print(
            f"  step {r['step']:3d}  loss {r['loss']:.3f}")},
    )
    print(f"trained: loss {result['history'][0]['loss']:.3f} -> "
          f"{result['history'][-1]['loss']:.3f}")

    prompts = jax.random.randint(jax.random.PRNGKey(0), (2, 8), 2, cfg.vocab)
    toks, stats = generate(result["params"], prompts, cfg, engine,
                           GenConfig(max_new_tokens=16, stop_on_eos=False))
    print(f"generated {toks.shape} tokens; "
          f"summarization {stats['prefill_sec']*1e3:.1f} ms, "
          f"generation {stats['sec_per_token']*1e3:.2f} ms/token")
    print("sample ids:", jnp.asarray(toks)[0][:12].tolist())


if __name__ == "__main__":
    main()
