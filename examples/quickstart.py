"""Quickstart: train a tiny LM for a few steps, checkpoint, restore, decode.

    PYTHONPATH=src python examples/quickstart.py

Uses the public API only: configs registry -> build_model -> TrainDriver
(prefetch + async checkpoint + restart) -> incremental decoding.
"""
import sys
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.data import DataConfig
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.optim import AdamWConfig, apply_updates, init_state
from repro.runtime import DriverConfig, TrainDriver


def main():
    use_compile_cache()
    cfg = get_config("llama3.2-1b").smoke_config().replace(
        d_model=128, d_ff=256, n_layers=2, vocab=512, remat=False)
    model = build_model(cfg)
    opt_cfg = AdamWConfig(lr=2e-3, warmup=10, total_steps=60)

    def init_fn():
        params = model.init(jax.random.PRNGKey(0), jnp.float32)
        return params, init_state(opt_cfg, params)

    @jax.jit
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, batch))(params)
        params, opt_state = apply_updates(opt_cfg, params, grads, opt_state)
        return params, opt_state, loss

    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=128, global_batch=8)
    driver = TrainDriver(
        DriverConfig(total_steps=60, ckpt_every=20,
                     ckpt_dir="/tmp/repro_quickstart"),
        data_cfg, train_step, init_fn)
    hist = driver.run()
    print(f"loss: {hist[0].loss:.3f} -> {hist[-1].loss:.3f} "
          f"over {len(hist)} steps")
    assert hist[-1].loss < hist[0].loss

    # restore the last checkpoint and decode a few tokens
    from repro.checkpoint import latest_step, restore
    params, opt_state = init_fn()
    step = latest_step("/tmp/repro_quickstart")
    state = restore("/tmp/repro_quickstart", step,
                    {"params": params, "opt": opt_state})
    params = state["params"]

    caches = model.init_cache(2, 64, jnp.float32)
    toks = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
    _, caches = model.forward(params, toks, caches=caches, pos_offset=0)
    tok = jnp.array([[7], [8]], jnp.int32)
    out = []
    for i in range(8):
        logits, caches = model.decode_step(params, tok, caches, 3 + i)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out.append(int(tok[0, 0]))
    print("greedy continuation:", out)
    print("quickstart OK")


if __name__ == "__main__":
    main()
