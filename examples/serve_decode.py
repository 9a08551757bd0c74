"""Batched serving example: prefill a batch of prompts, decode with caches.

    PYTHONPATH=src python examples/serve_decode.py

Drives three different architecture families through the same serving API:
a dense GQA model, the MLA (compressed-cache) model, and the attention-free
RWKV6 — demonstrating that the cache abstraction covers KV caches,
low-rank latent caches, and constant-size recurrent state.
"""
import sys
sys.path.insert(0, "src")

import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model

ARCHS = ["llama3.2-1b", "deepseek-v3-671b", "rwkv6-1.6b"]


def main():
    use_compile_cache()
    for arch in ARCHS:
        cfg = get_config(arch).smoke_config().replace(remat=False)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0), jnp.float32)
        B, Lp, G = 4, 16, 16
        prompts = jax.random.randint(jax.random.PRNGKey(1), (B, Lp), 0,
                                     cfg.vocab).astype(jnp.int32)
        caches = model.init_cache(B, Lp + G + 1, jnp.float32)

        @jax.jit
        def prefill(params, caches, toks):
            logits, caches = model.forward(params, toks, caches=caches,
                                           pos_offset=0)
            return logits[:, -1], caches

        @jax.jit
        def step(params, caches, tok, pos):
            return model.decode_step(params, tok, caches, pos)

        logits, caches = prefill(params, caches, prompts)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        t0 = time.time()
        toks = [tok]
        for i in range(G - 1):
            logits, caches = step(params, caches, tok, Lp + i)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            toks.append(tok)
        dt = (time.time() - t0) / (G - 1) * 1e3
        cache_bytes = sum(x.size * x.dtype.itemsize
                          for x in jax.tree.leaves(caches))
        print(f"{arch:20s} decode {dt:6.1f} ms/step  "
              f"cache={cache_bytes/1e6:.2f} MB  "
              f"sample={[int(t[0,0]) for t in toks[:6]]}")
    print("serve_decode OK")


if __name__ == "__main__":
    main()
