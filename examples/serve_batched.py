"""Continuous-batching serving with ``ServeEngine``: requests of mixed
length share a paged KV cache and a single fixed-shape decode jit —
admitted as slots free up, evicted the step they finish.

Runs a reduced-family model on CPU, serves a batch of prompts (greedy
plus a couple of sampled requests), and verifies the engine's batched
output is token-identical to serving one request at a time.

    PYTHONPATH=src python examples/serve_batched.py --arch qwen2-0.5b \
        [--requests 8] [--gen 16] [--concurrency 4]

To serve a ``launch/train.py --save`` artifact instead of fresh params:

    PYTHONPATH=src python -m repro.launch.train --smoke --save ckpt/
    PYTHONPATH=src python examples/serve_batched.py --ckpt ckpt/
"""
import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tr
from repro.serve import SamplingParams, ServeEngine, ServeSettings

KEY = jax.random.PRNGKey(0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--concurrency", type=int, default=4)
    ap.add_argument("--ckpt", default=None,
                    help="serve a launch/train.py --save artifact")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).smoke()
    settings = ServeSettings(max_concurrency=args.concurrency,
                             block_size=16, num_blocks=128,
                             max_model_len=64 + args.gen,
                             max_new_tokens=args.gen,
                             cache_dtype="float32")
    if args.ckpt:
        engine = ServeEngine.from_checkpoint(args.ckpt, cfg, settings)
    else:
        engine = ServeEngine(cfg, tr.init_params(KEY, cfg), settings)
    print(f"arch={cfg.name} family={cfg.family} "
          f"requests={args.requests} concurrency={args.concurrency}")

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab,
                              size=int(rng.integers(4, 24))).tolist()
        samp = (SamplingParams() if i % 3 else
                SamplingParams(temperature=0.8, top_k=20, top_p=0.95))
        engine.submit(prompt, sampling=samp, seed=i)

    outs = []
    while engine.waiting or engine._active():
        outs.extend(engine.step())
    outs.sort(key=lambda o: o.rid)
    st = engine.stats()
    print(f"decode: {st['steps']} engine steps, {st['tokens_out']} tokens "
          f"({st['tokens_per_s']:.1f} tok/s), peak blocks "
          f"{st['peak_blocks']}/{st['block_capacity']}")

    # ---- batching invariance: each request alone gives the same stream
    import dataclasses
    solo_settings = dataclasses.replace(settings, max_concurrency=1)
    agree = 0
    for o in outs:
        solo = (ServeEngine.from_checkpoint(args.ckpt, cfg, solo_settings)
                if args.ckpt else
                ServeEngine(engine.cfg, engine.params, solo_settings))
        samp = (SamplingParams() if o.rid % 3 else
                SamplingParams(temperature=0.8, top_k=20, top_p=0.95))
        solo.submit(o.prompt, sampling=samp, seed=o.rid)
        agree += solo.run()[0].tokens == o.tokens
    print(f"batched vs solo token identity: {agree}/{len(outs)}")
    for o in outs[:2]:
        print(f"  request {o.rid}: prompt={o.prompt[:8]}... "
              f"-> generated={o.tokens[:10]}... "
              f"({o.finish_reason}, ttft {o.ttft_s*1e3:.0f}ms)")


if __name__ == "__main__":
    main()
