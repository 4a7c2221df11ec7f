#!/usr/bin/env python
"""Text generation timing anatomy (paper Figs. 1/2): summarization vs
generation stage scaling, exact vs LUT nonlinearities, optional int8
decode path.

    PYTHONPATH=src python examples/generate_text.py --arch gpt2-medium --smoke
"""
import argparse

import jax

from repro.configs import get_config
from repro.core.salpim import SalPimConfig, SalPimEngine
from repro.models import api
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving.engine import GenConfig, generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-medium")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--out-sizes", default="4,16,64")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    print(f"{cfg.name}: {cfg.param_count():,} params")

    for mode, quant in (("exact", "none"), ("lut", "none"), ("exact", "int8")):
        engine = SalPimEngine.create(
            SalPimConfig(nonlinear_mode=mode, quant=quant))
        prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                     2, cfg.vocab)
        line = [f"nonlin={mode:5s} quant={quant:4s}:"]
        for n_out in map(int, args.out_sizes.split(",")):
            toks, stats = generate(
                params, prompts, cfg, engine,
                GenConfig(max_new_tokens=n_out, stop_on_eos=False))
            line.append(f"out={n_out}: {stats['decode_sec']*1e3:7.1f}ms"
                        f" ({stats['sec_per_token']*1e3:5.2f}ms/tok)")
        print("  ".join(line))
    print("note: generation time scales ~linearly with output size; the"
          " prefill (summarization) cost is paid once — paper Fig. 1.")


if __name__ == "__main__":
    main()
