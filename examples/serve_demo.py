"""Batched serving demo: prefill + KV-cache decode across architecture
families (dense GQA / MoE / RG-LRU hybrid / RWKV6), exercising the same
caches the decode_32k / long_500k dry-run shapes lower.

    PYTHONPATH=src python examples/serve_demo.py [--arch rwkv6-7b]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.models import transformer as T
from repro.serve import generate


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="one arch id (default: a family sample)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()

    arches = [args.arch] if args.arch else \
        ["qwen3-1.7b", "granite-moe-1b-a400m", "recurrentgemma-9b",
         "rwkv6-7b"]
    for arch in arches:
        cfg = get_config(arch).reduced()
        params, _ = T.init(cfg, jax.random.PRNGKey(0))
        prompts = jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len),
            0, cfg.vocab)
        t0 = time.time()
        out = generate(params, cfg, prompts, args.max_new, temperature=0.7,
                       key=jax.random.PRNGKey(2))
        dt = time.time() - t0
        print(f"{arch:22s} served batch={args.batch} "
              f"prompt={args.prompt_len} new={args.max_new} "
              f"in {dt:5.1f}s -> tokens shape {out.tokens.shape}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
