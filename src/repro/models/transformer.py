"""Decoder-only LM covering dense / MoE / MLA / VLM-prefix families.

Design notes (these matter for the 512-device dry-run):
  * layers are stacked ([L, ...] leading dim) and iterated with `lax.scan`,
    so the HLO size is O(1) in depth;
  * MoE models with a dense prefix (DeepSeek) use two scans;
  * remat (`jax.checkpoint`) wraps the scan body when cfg.remat;
  * the VLM/audio frontend is a stub: precomputed patch/frame embeddings are
    concatenated in front of the token embeddings (per assignment).
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .config import ArchConfig
from .layers import (gqa_apply, gqa_params, mla_apply, mla_params, mlp_apply,
                     mlp_params, moe_einsum_apply, moe_ep_apply, moe_params,
                     rmsnorm)

PyTree = Any


@dataclass
class ParallelCtx:
    """Parallel execution context for layers needing explicit collectives.

    None mesh => single-device semantics (smoke tests).  When a mesh is
    present, MoE layers with impl='ep_a2a' run inside a shard_map region:
    tokens sharded (dp_spec x 'model' on sequence), experts sharded over
    ``ep_axis``, with explicit all-to-all dispatch (DeepSeek-style EP).
    """
    ep_axis: Optional[str] = None
    ep_size: int = 1
    mesh: Any = None
    dp_spec: Any = None      # PartitionSpec entry for the batch dim


def _stack(key, n: int, init_fn: Callable) -> PyTree:
    keys = jax.random.split(key, n)
    return (jax.vmap(init_fn)(keys) if False else
            jax.tree.map(lambda *xs: jnp.stack(xs), *[init_fn(k) for k in keys]))


def _layer_params(key, cfg: ArchConfig, dtype, moe_layer: bool):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "ln1": jnp.ones((cfg.d_model,), dtype),
        "ln2": jnp.ones((cfg.d_model,), dtype),
        "attn": (mla_params(k1, cfg, dtype) if cfg.mla
                 else gqa_params(k1, cfg, dtype)),
    }
    if moe_layer:
        p["moe"] = moe_params(k2, cfg, dtype)
    else:
        p["mlp"] = mlp_params(k2, cfg.d_model, cfg.d_ff, cfg.mlp, dtype)
    return p


def init_params(cfg: ArchConfig, key, dtype=jnp.bfloat16) -> PyTree:
    ke, kl, kd, ko = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(cfg.d_model)
    params: dict = {
        "embed": (jax.random.normal(ke, (cfg.vocab, cfg.d_model)) * s
                  ).astype(dtype),
        "ln_f": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = (jax.random.normal(ko, (cfg.d_model, cfg.vocab))
                             * s).astype(dtype)
    n_moe = (cfg.n_layers - cfg.n_dense_layers) if cfg.moe else 0
    n_dense = cfg.n_layers - n_moe
    if n_dense:
        params["layers"] = _stack(
            kl, n_dense, lambda k: _layer_params(k, cfg, dtype, False))
    if n_moe:
        params["moe_layers"] = _stack(
            kd, n_moe, lambda k: _layer_params(k, cfg, dtype, True))
    return params


def seq_shard(x, ctx: ParallelCtx, enable: bool = True):
    """Sequence-parallel residual: shard S over 'model' between blocks.

    Megatron-SP style — the saved activation per scanned layer becomes
    [B/dp, S/model, d] instead of [B/dp, S, d]; attention/MoE regions gather
    the sequence where they need it (XLA inserts the all-gather).  Disabling
    it (cfg.seq_shard_residual=False) trades ~L x [B,S,d] of extra HBM for
    the removal of the per-layer sequence gathers — the right trade when the
    cell is collective-bound and under the HBM budget (§Perf).
    """
    if ctx is None or ctx.mesh is None or x.ndim != 3:
        return x
    if enable and x.shape[1] % ctx.mesh.shape["model"] == 0:
        return wsc(x, ctx, ctx.dp_spec, "model", None)
    return wsc(x, ctx, ctx.dp_spec, None, None)


def _block(cfg: ArchConfig, p, x, positions, cache, moe_layer: bool,
           ctx: ParallelCtx, window: int = 0):
    h = rmsnorm(p["ln1"], x, cfg.rms_eps)
    if cfg.mla:
        a, new_cache = mla_apply(p["attn"], h, cfg, positions=positions,
                                 cache=cache, ctx=ctx)
    else:
        a, new_cache = gqa_apply(p["attn"], h, cfg, positions=positions,
                                 cache=cache, window=window, ctx=ctx)
    # §Perf: constrain the row-parallel projection OUTPUT to the SP layout so
    # its partial sums lower to reduce-scatter instead of all-reduce +
    # re-gather (the Megatron-SP identity).
    a = seq_shard(a, ctx, cfg.seq_shard_residual)
    x = x + a
    h = rmsnorm(p["ln2"], x, cfg.rms_eps)
    if moe_layer:
        f = _moe_dispatch(cfg, p["moe"], h, ctx)
    else:
        f = mlp_apply(p["mlp"], h, cfg.mlp)
    f = seq_shard(f, ctx, cfg.seq_shard_residual)
    return seq_shard(x + f, ctx, cfg.seq_shard_residual), new_cache


def _moe_dispatch(cfg: ArchConfig, pmoe, h, ctx: ParallelCtx):
    """Pick the MoE execution strategy.

    * few tokens (decode) or no mesh: grouped einsum dispatch (pjit shards it)
    * impl='ep_a2a' + mesh: shard_map expert parallelism — tokens sharded
      (batch over dp, sequence over 'model'), experts over 'model', explicit
      all-to-all (the DeepSeek EP pattern).
    """
    B, S, _ = h.shape
    T = B * S
    use_ep = (cfg.moe.impl == "ep_a2a" and ctx.mesh is not None
              and T >= cfg.moe.ep_threshold
              and S % ctx.mesh.shape["model"] == 0)
    if not use_ep:
        if cfg.moe.impl == "ep_a2a" and ctx.mesh is None and T >= 8192:
            # large token count without a mesh: still exercise the EP path
            return moe_ep_apply(pmoe, h, cfg, ep_axis=None, ep_size=1)
        return moe_einsum_apply(pmoe, h, cfg)

    from jax.sharding import PartitionSpec as P
    from ..parallel.sharding import _axis_size, _fit_axis
    # EP spans (data x model) when the expert count divides (DeepSeek: 256
    # experts over the whole 256-chip pod, one expert per chip); otherwise
    # just the model axis.  Must match the storage sharding of the experts.
    ep_axis = _fit_axis(("data", "model"), cfg.moe.n_experts, ctx.mesh)
    if ep_axis is None:
        return moe_einsum_apply(pmoe, h, cfg)
    ep_size = _axis_size(ctx.mesh, ep_axis)
    tok_spec = P(ctx.dp_spec, "model", None)
    routed = {k: v for k, v in pmoe.items() if k != "shared"}
    pspecs = {"router": P(None, None),
              "wg": P(ep_axis, None, None),
              "wu": P(ep_axis, None, None),
              "wd": P(ep_axis, None, None)}

    def region(xx, pp):
        # routed experts only: the shared expert is TP-sharded at pjit level
        # (inside the region its ff-sharded matmul would be a partial sum).
        cfg_routed = cfg.replace(moe=dataclasses.replace(cfg.moe, n_shared=0))
        return moe_ep_apply(pp, xx, cfg_routed, ep_axis=ep_axis,
                            ep_size=ep_size)

    out = jax.shard_map(region, mesh=ctx.mesh,
                        in_specs=(tok_spec, pspecs),
                        out_specs=tok_spec, check_vma=False)(h, routed)
    if cfg.moe.n_shared:
        out = out + mlp_apply(pmoe["shared"], h, "swiglu")
    return out


def _scan_blocks(cfg: ArchConfig, stacked, x, positions, caches, moe: bool,
                 ctx: ParallelCtx, window: int = 0):
    """lax.scan over the stacked layer params (cache is scanned along L)."""

    def body(carry, inp):
        x = carry
        p, cache = inp
        x, new_cache = _block(cfg, p, x, positions, cache, moe, ctx, window)
        return x, new_cache

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, new_caches = jax.lax.scan(body, x, (stacked, caches))
    return x, new_caches


def _unrolled_blocks(cfg, stacked, x, positions, caches, moe, ctx, window=0):
    n = jax.tree.leaves(stacked)[0].shape[0]
    new_caches = []
    for i in range(n):
        p = jax.tree.map(lambda a: a[i], stacked)
        c = None if caches is None else jax.tree.map(lambda a: a[i], caches)
        blk = partial(_block, cfg)
        if cfg.remat:
            blk = jax.checkpoint(blk, static_argnums=(5, 6, 7))
        x, nc = _block(cfg, p, x, positions, c, moe, ctx, window)
        new_caches.append(nc)
    if caches is None:
        return x, None
    return x, jax.tree.map(lambda *xs: jnp.stack(xs), *new_caches)


def wsc(x, ctx: ParallelCtx, *spec):
    """with_sharding_constraint when a mesh is present (no-op otherwise)."""
    if ctx is None or ctx.mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*spec)))


def _embed(cfg: ArchConfig, params, tokens, extra_embeds=None):
    x = params["embed"][tokens]
    if extra_embeds is not None:
        # VLM/audio stub: prefix precomputed embeddings
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    return x


@partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _unembed_mm(x, w, ctx, transpose_w):
    return x @ (w.T if transpose_w else w)


def _unembed_fwd(x, w, ctx, transpose_w):
    return _unembed_mm(x, w, ctx, transpose_w), (x, w)


def _unembed_bwd(ctx, transpose_w, res, g):
    """§Perf (iteration 9): the default VJP materializes a FULL unsharded f32
    [d, V] weight gradient per device (~3.7 GB x3 at deepseek scale).  Here
    the cotangent is cast to bf16 (MXU still accumulates f32 internally) and
    the weight grad is sharding-constrained to the weight's own layout, so
    the partial sums reduce-scatter instead of replicating."""
    x, w = res
    gb = g.astype(w.dtype)
    dx = (gb @ (w if transpose_w else w.T)).astype(x.dtype)
    d_flat = x.reshape(-1, x.shape[-1])
    g_flat = gb.reshape(-1, gb.shape[-1])
    dw = jax.lax.dot_general(d_flat, g_flat, (((0,), (0,)), ((), ())),
                             preferred_element_type=w.dtype)  # [d, V]
    if transpose_w:
        dw = dw.T                                             # [V, d]
    if ctx is not None and ctx.mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        model_n = ctx.mesh.shape["model"]
        if transpose_w:   # tied embedding [V, d]
            spec = P("model" if w.shape[0] % model_n == 0 else None, None)
        else:             # unembed [d, V]
            spec = P(None, "model" if w.shape[1] % model_n == 0 else None)
        dw = jax.lax.with_sharding_constraint(
            dw, NamedSharding(ctx.mesh, spec))
    return dx, dw.astype(w.dtype)


_unembed_mm.defvjp(_unembed_fwd, _unembed_bwd)


def _unembed(cfg: ArchConfig, params, x, ctx: ParallelCtx = None):
    if cfg.tie_embeddings:
        return _unembed_mm(x, params["embed"], ctx, True)
    return _unembed_mm(x, params["unembed"], ctx, False)


def forward(cfg: ArchConfig, params, tokens, *, extra_embeds=None,
            caches=None, pos_offset=0, ctx: ParallelCtx = ParallelCtx(),
            window: Optional[int] = None):
    """Full forward pass. tokens [B,S] -> logits [B,S_total,V].

    caches: per-family cache pytree (see ``init_cache``) for incremental
    decoding; pos_offset is the absolute position of tokens[:,0].
    """
    window = cfg.sliding_window if window is None else window
    x = _embed(cfg, params, tokens, extra_embeds)
    if x.shape[1] > 1:
        x = wsc(x, ctx, ctx.dp_spec, None, None)
    S = x.shape[1]
    positions = jnp.arange(S) + pos_offset
    n_moe = (cfg.n_layers - cfg.n_dense_layers) if cfg.moe else 0
    n_dense = cfg.n_layers - n_moe
    new_caches = {}
    run = _scan_blocks if cfg.scan_layers else _unrolled_blocks
    if n_dense:
        c = caches.get("dense") if caches else None
        x, nc = run(cfg, params["layers"], x, positions, c, False, ctx, window)
        new_caches["dense"] = nc
    if n_moe:
        c = caches.get("moe") if caches else None
        x, nc = run(cfg, params["moe_layers"], x, positions, c, True, ctx, window)
        new_caches["moe"] = nc
    x = rmsnorm(params["ln_f"], x, cfg.rms_eps)
    logits = _unembed(cfg, params, x, ctx)
    return (logits, new_caches if caches is not None else None)


def xent(logits, labels, ctx: ParallelCtx = ParallelCtx()):
    """Sharded cross entropy that never materializes unsharded f32 logits.

    Preferred layout: sequence-sharded logits (dp, 'model', None) — every
    reduction is vocab-local, gradients stay sharded, and the only extra
    collective is the small unembed-wgrad all-reduce.  Falls back to
    vocab-sharded (dp, None, 'model') when S doesn't divide the model axis.
    The gold logit is a one-hot *contraction*, not a gather: SPMD partitions
    the fused compare-select-reduce without an all-gather (a gather along a
    sharded vocab axis would re-materialize [B,S,V] f32 per device).
    """
    if ctx is not None and ctx.mesh is not None:
        if logits.shape[1] % ctx.mesh.shape["model"] == 0:
            logits = wsc(logits, ctx, ctx.dp_spec, "model", None)
        else:
            logits = wsc(logits, ctx, ctx.dp_spec, None, "model")
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    gold = jnp.einsum("bsv,bsv->bs", lf, onehot)
    mask = (labels >= 0).astype(jnp.float32)
    nll = (lse - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


def loss_fn(cfg: ArchConfig, params, batch, ctx: ParallelCtx = ParallelCtx()):
    """Next-token cross-entropy; batch = {tokens, labels[, extra_embeds]}."""
    logits, _ = forward(cfg, params, batch["tokens"],
                        extra_embeds=batch.get("extra_embeds"), ctx=ctx)
    labels = batch["labels"]
    if batch.get("extra_embeds") is not None:
        # loss only on text positions: pad labels with -1 over the modality
        # prefix instead of slicing logits (slicing a sequence-sharded logits
        # tensor would force an unsharded materialization).
        prefix = logits.shape[1] - labels.shape[1]
        labels = jnp.concatenate(
            [jnp.full(labels.shape[:1] + (prefix,), -1, labels.dtype),
             labels], axis=1)
    return xent(logits, labels, ctx)


# ----------------------------------------------------------------- caches
def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Stacked per-layer decode caches."""
    def one(kind: str):
        if cfg.mla:
            m = cfg.mla
            return {"c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
                    "k_rope": jnp.zeros((batch, max_len, m.qk_rope_head_dim),
                                        dtype),
                    "len": jnp.zeros((), jnp.int32)}
        hd = cfg.hd()
        if cfg.sliding_window and cfg.sliding_window < max_len:
            W = cfg.sliding_window
            return {"k": jnp.zeros((batch, W, cfg.n_kv_heads, hd), dtype),
                    "v": jnp.zeros((batch, W, cfg.n_kv_heads, hd), dtype),
                    "pos": jnp.full((W,), -1, jnp.int32),
                    "len": jnp.zeros((), jnp.int32)}
        return {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
                "len": jnp.zeros((), jnp.int32)}

    n_moe = (cfg.n_layers - cfg.n_dense_layers) if cfg.moe else 0
    n_dense = cfg.n_layers - n_moe
    out = {}
    if n_dense:
        out["dense"] = jax.tree.map(
            lambda x: jnp.stack([x] * n_dense), one("dense"))
    if n_moe:
        out["moe"] = jax.tree.map(
            lambda x: jnp.stack([x] * n_moe), one("moe"))
    return out


def decode_step(cfg: ArchConfig, params, tokens1, caches, pos,
                ctx: ParallelCtx = ParallelCtx()):
    """One incremental decode step: tokens1 [B,1] at absolute position pos."""
    logits, new_caches = forward(cfg, params, tokens1, caches=caches,
                                 pos_offset=pos, ctx=ctx)
    return logits[:, -1], new_caches
