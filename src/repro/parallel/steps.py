"""Train / prefill / decode step builders (pjit programs).

Each builder returns a pure function plus its (in/out) sharding trees so the
same object serves the real launcher and the dry-run's
``jax.jit(...).lower(...)``.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.config import ModelConfig, ParallelConfig, TrainConfig
from repro.models import transformer as T
from repro.models import encdec as E
from repro.models.moe import MeshCtx
from repro import optim
from .sharding import (param_specs, opt_specs, scatter_specs, to_shardings,
                       batch_spec)

Pytree = Any


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def cross_entropy(logits: jax.Array, labels: jax.Array, *,
                  z_loss: float = 0.0, chunk: Optional[int] = None) -> jax.Array:
    """Token-mean CE over (B, S, V) f32 logits; vocab may be model-sharded —
    the label pick uses an iota-mask reduction (shardable, no gather)."""

    def _ce(lg, lb):
        lg = lg.astype(jnp.float32)
        m = jnp.max(lg, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1)) + m[..., 0]
        vocab_iota = lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
        picked = jnp.sum(jnp.where(vocab_iota == lb[..., None], lg, 0.0), axis=-1)
        loss = lse - picked
        if z_loss:
            loss = loss + z_loss * lse ** 2
        return jnp.sum(loss), loss.size

    if chunk is None:
        total, n = _ce(logits, labels)
        return total / n
    # sequence-chunked CE (bounds the (B, Sc, V) f32 transient); pad the
    # remainder with an ignored label (-1 never matches the vocab iota and
    # its lse contribution is subtracted via the weight mask)
    s = logits.shape[1]
    pad = (-s) % chunk
    if pad:
        logits = jnp.pad(logits, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    sp = s + pad
    lg = logits.reshape(logits.shape[0], sp // chunk, chunk, -1)
    lb = labels.reshape(labels.shape[0], sp // chunk, chunk)

    def body(acc, xs):
        lgc, lbc = xs
        lgf = lgc.astype(jnp.float32)
        m = jnp.max(lgf, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(lgf - m), axis=-1)) + m[..., 0]
        iota = lax.broadcasted_iota(jnp.int32, lgf.shape, lgf.ndim - 1)
        picked = jnp.sum(jnp.where(iota == lbc[..., None], lgf, 0.0), axis=-1)
        w = (lbc >= 0).astype(jnp.float32)
        return acc + jnp.sum((lse - picked) * w), None

    total, _ = lax.scan(body, jnp.zeros((), jnp.float32),
                        (jnp.moveaxis(lg, 1, 0), jnp.moveaxis(lb, 1, 0)))
    return total / labels_size_orig(labels, pad)


def labels_size_orig(padded_labels, pad):
    b, sp = padded_labels.shape
    return b * (sp - pad)


def make_loss_fn(cfg: ModelConfig, pcfg: ParallelConfig, tcfg: TrainConfig,
                 ctx: Optional[MeshCtx]):
    def loss_fn(params, batch):
        if cfg.enc_dec:
            logits, aux = E.forward(params, batch["frames"], batch["tokens"], cfg,
                                    remat=pcfg.remat, ctx=ctx,
                                    unroll=pcfg.scan_unroll)
        else:
            logits, aux = T.forward(params, batch["tokens"], cfg, ctx=ctx,
                                    remat=pcfg.remat, unroll=pcfg.scan_unroll)
        if ctx is not None:
            vpart = None if getattr(ctx, "dp_over_model", False) else "model"
            logits = lax.with_sharding_constraint(
                logits, NamedSharding(ctx.mesh, P(ctx.batch_axes, None, vpart)))
        loss = cross_entropy(logits[:, :-1], batch["tokens"][:, 1:],
                             z_loss=tcfg.z_loss, chunk=pcfg.logit_chunk)
        loss = loss + 1e-2 * aux  # MoE load-balance
        return loss, {"loss": loss, "aux": aux}
    return loss_fn


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------
def make_train_step(cfg: ModelConfig, pcfg: ParallelConfig, tcfg: TrainConfig,
                    ctx: Optional[MeshCtx]) -> Callable:
    """Train step for a layout: dispatches on ``pcfg.grad_reduce`` — the
    classic all-reduce step, or the ZeRO reduce-scatter step when a mesh ctx
    is available to scatter over."""
    if pcfg.grad_reduce == "reduce_scatter_zero":
        if ctx is not None:
            return make_train_step_zero(cfg, pcfg, tcfg, ctx)
        import warnings
        warnings.warn("grad_reduce='reduce_scatter_zero' needs a mesh ctx; "
                      "falling back to the single-device all-reduce step",
                      stacklevel=2)
    loss_fn = make_loss_fn(cfg, pcfg, tcfg, ctx)

    def train_step(state: Pytree, batch: Pytree) -> Tuple[Pytree, Pytree]:
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], batch)
        if pcfg.grad_barrier:
            # pin the gradient reductions in their native (bf16) dtype: the
            # barrier stops XLA from sinking the all-reduce past the f32
            # converts of the optimizer math (§Perf A6)
            grads = lax.optimization_barrier(grads)
        if pcfg.grad_dtype != "float32":
            grads = jax.tree.map(lambda g: g.astype(pcfg.grad_dtype), grads)
        grads, gnorm = optim.clip_by_global_norm(grads, tcfg.grad_clip)
        lr = optim.warmup_cosine(state["opt"]["step"], lr=tcfg.lr,
                                 warmup_steps=tcfg.warmup_steps,
                                 total_steps=tcfg.total_steps)
        params, opt_state = optim.adamw_update(
            grads, state["opt"], state["params"], lr=lr, b1=tcfg.b1, b2=tcfg.b2,
            weight_decay=tcfg.weight_decay)
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return {"params": params, "opt": opt_state}, metrics

    return train_step


def make_train_step_zero(cfg: ModelConfig, pcfg: ParallelConfig,
                         tcfg: TrainConfig, ctx: MeshCtx) -> Callable:
    """ZeRO train step: grads reduce-scattered over the fsdp (else data)
    axes, AdamW updates only the local shard, params all-gathered for the
    next forward (``optim.adamw_update_zero``).

    Loss/grad/clip are token-for-token the all-reduce step — the clip norm
    is taken on the reduced grads *before* the scatter so the two steps'
    trajectories coincide; only the layout of the optimizer segment (and
    hence its comm pattern: Θ(m·(p-1)/p) reduce-scatter + all-gather instead
    of the Θ(2m·(p-1)/p) all-reduce feeding p redundant full updates)
    differs."""
    if ctx is None:
        raise ValueError("make_train_step_zero needs a mesh ctx to scatter "
                         "over; use make_train_step on a single device")
    loss_fn = make_loss_fn(cfg, pcfg, tcfg, ctx)

    def train_step(state: Pytree, batch: Pytree) -> Tuple[Pytree, Pytree]:
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state["params"], batch)
        scatter = to_shardings(scatter_specs(state["params"], cfg, ctx),
                               ctx.mesh)
        gather = to_shardings(param_specs(state["params"], cfg, ctx), ctx.mesh)
        # the reduced grads live in the params' layout; without this pin the
        # scatter layout propagates back into the backward pass and the
        # partitioner re-orders the embedding's scatter-add sums
        grads = lax.with_sharding_constraint(grads, gather)
        if pcfg.grad_barrier:
            grads = lax.optimization_barrier(grads)
        if pcfg.grad_dtype != "float32":
            grads = jax.tree.map(lambda g: g.astype(pcfg.grad_dtype), grads)
        grads, gnorm = optim.clip_by_global_norm(grads, tcfg.grad_clip)
        lr = optim.warmup_cosine(state["opt"]["step"], lr=tcfg.lr,
                                 warmup_steps=tcfg.warmup_steps,
                                 total_steps=tcfg.total_steps)
        params, opt_state = optim.adamw_update_zero(
            grads, state["opt"], state["params"], scatter=scatter,
            gather=gather, lr=lr, b1=tcfg.b1, b2=tcfg.b2,
            weight_decay=tcfg.weight_decay)
        metrics = dict(metrics, grad_norm=gnorm, lr=lr)
        return {"params": params, "opt": opt_state}, metrics

    return train_step


def init_train_state(rng, cfg: ModelConfig, pcfg: ParallelConfig) -> Pytree:
    init = E.init if cfg.enc_dec else T.init
    params = init(rng, cfg)
    opt = optim.adamw_init(params, pcfg.opt_state_dtype,
                           master=pcfg.master_weights)
    if pcfg.master_weights:
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), params)
    return {"params": params, "opt": opt}


def abstract_train_state(cfg: ModelConfig, pcfg: ParallelConfig) -> Pytree:
    return jax.eval_shape(partial(init_train_state, cfg=cfg, pcfg=pcfg),
                          jax.random.PRNGKey(0))


def train_state_shardings(cfg: ModelConfig, pcfg: ParallelConfig,
                          ctx: MeshCtx, state: Pytree) -> Pytree:
    pspec = param_specs(state["params"], cfg, ctx)
    sspec = scatter_specs(state["params"], cfg, ctx) \
        if pcfg.grad_reduce == "reduce_scatter_zero" else None
    ospec = opt_specs(pspec, sspec)
    if "master" in state["opt"]:
        ospec["master"] = sspec if sspec is not None else pspec
    tree = {"params": pspec, "opt": ospec}
    return to_shardings(tree, ctx.mesh)


# ---------------------------------------------------------------------------
# Serve steps
# ---------------------------------------------------------------------------
def make_prefill_step(cfg: ModelConfig, pcfg: ParallelConfig,
                      ctx: Optional[MeshCtx]) -> Callable:
    """Fused prefill: one cache-writing full-sequence forward per prompt —
    ``prefill(params, batch, cache)`` returns ``(last_logits, cache)``
    (enc-dec additionally returns the encoder output the decode steps need).
    ``batch`` may carry per-row true prompt ``length``s for right-padded
    prompts (attention patterns only; pad entries are causally invisible)."""

    def prefill(params, batch, cache):
        length = batch.get("length")
        if cfg.enc_dec:
            enc = E.encode(params, batch["frames"], cfg, remat="none", ctx=ctx,
                           unroll=pcfg.scan_unroll)
            logits, cache = E.decode_prefill(params, batch["tokens"], enc, cache,
                                             cfg, length=length, ctx=ctx,
                                             unroll=pcfg.scan_unroll)
            return logits, cache, enc
        logits, cache = T.prefill(params, batch["tokens"], cache, cfg,
                                  length=length, ctx=ctx,
                                  unroll=pcfg.scan_unroll)
        return logits, cache

    return prefill


def make_decode_step(cfg: ModelConfig, pcfg: ParallelConfig,
                     ctx: Optional[MeshCtx], *,
                     return_logits: bool = False,
                     paged: bool = False) -> Callable:
    """Decode step: greedy (argmax token) by default; ``return_logits``
    hands back the f32 logits instead so the scheduler can sample
    (temperature / top-p) in its slot loop.  ``paged``: the step takes
    ``(params, tok, cache, pos, block_tables)`` — the cache is the shared
    page arena and every request reads/writes through its table row, so the
    paged and end-aligned modes share one fixed-shape engine."""
    if paged and cfg.enc_dec:
        raise NotImplementedError("paged decode is decoder-only")

    def decode(params, token, cache, pos, enc_out=None):
        if cfg.enc_dec:
            logit, new_cache = E.decode_step(params, token, cache, pos, enc_out, cfg,
                                             unroll=pcfg.scan_unroll, ctx=ctx)
        else:
            logit, new_cache = T.decode_step(params, token, cache, pos, cfg, ctx=ctx,
                                             unroll=pcfg.scan_unroll)
        if return_logits:
            return logit.astype(jnp.float32), new_cache
        return jnp.argmax(logit, axis=-1).astype(jnp.int32), new_cache

    def decode_paged(params, token, cache, pos, block_tables):
        # a stable name for the step in device traces (op metadata)
        with jax.named_scope("decode_paged"):
            logit, new_cache = T.decode_step(params, token, cache, pos, cfg,
                                             ctx=ctx, unroll=pcfg.scan_unroll,
                                             block_tables=block_tables)
            if return_logits:
                return logit.astype(jnp.float32), new_cache
            return jnp.argmax(logit, axis=-1).astype(jnp.int32), new_cache

    return decode_paged if paged else decode


def make_chunk_prefill_step(cfg: ModelConfig, pcfg: ParallelConfig,
                            ctx: Optional[MeshCtx]) -> Callable:
    """Chunked-prefill step for the paged engine: one fixed-shape (1, chunk)
    slice of one request's prompt per call — K/V written into freshly
    allocated pages through the block table (and the windowed layers' ring
    of the request's ``slot``), ``(last_logits, cache)`` back
    (``models.transformer.prefill_paged``).  Fixed chunk shape means ONE
    compile regardless of prompt length, and the per-call cost bounds the
    stall any admission can inflict on in-flight decodes
    (``costmodel.chunked_prefill_cost``)."""
    if cfg.enc_dec:
        raise NotImplementedError("chunked prefill is decoder-only")

    def chunk_prefill(params, tokens, cache, pos0, block_tables, length,
                      slot=0):
        with jax.named_scope("chunk_prefill"):
            return T.prefill_paged(params, tokens, cache, cfg, pos0=pos0,
                                   block_tables=block_tables, length=length,
                                   slot=slot, ctx=ctx,
                                   unroll=pcfg.scan_unroll)

    return chunk_prefill
