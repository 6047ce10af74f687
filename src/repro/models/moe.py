"""Mixture-of-Experts layer with expert parallelism on the FooPar algebra.

Design (DESIGN.md §3): after the attention block the activations are
replicated over the ``model`` axis (row-parallel all-reduce output), so every
(data-shard, model-shard) device already holds its batch shard's tokens.
Expert parallelism therefore needs **no all-to-all dispatch**: device
(d, m) locally selects the assignments of its tokens to *its* experts
(``mapD``), computes them with ``jax.lax.ragged_dot`` (sorted, grouped), and
the per-shard partial outputs are combined with one ``reduceD('sum')`` over
``model`` — the same single all-reduce a dense row-parallel FFN costs.
Table-1 cost: Θ(log p (t_s + t_w·T·d)) — vs an a2a dispatch+return
Θ(2 t_w·T·k/ep·d); the a2a variant is a §Perf hillclimb candidate.

Two layouts, auto-selected:
  * ``ep``: experts sharded over ``model`` (needs n_experts % ep == 0);
    capacity-dropped selection per shard (Kimi-K2: 384/16 = 24 per shard).
  * ``tp``: expert count < mesh axis (Mixtral: 8 < 16) — every shard computes
    all experts on a 1/ep slice of d_ff (dropless), same final psum.

The layer is a *full-manual* ``shard_map`` over every mesh axis: token
selection (sort/gather) stays device-local by construction, exactly the
paper's static process↔data mapping discipline.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.core.compat import shard_map as _shard_map
from repro.models.layers import dense_init, _dtype, _pdtype

Params = dict


@dataclass(frozen=True)
class MeshCtx:
    """Where a model call runs: mesh + role of each axis."""
    mesh: jax.sharding.Mesh
    batch_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    fsdp_axes: Tuple[str, ...] = ("data",)   # axes params are sharded over
    moe_a2a_ep: bool = False                 # token-routing EP (§Perf H6)
    engine_replicate: bool = False           # SSM/mLSTM engine batch-shard only
    seq_parallel: bool = False               # S-sharded residual (§Perf H5)
    foopar_tp: bool = False                  # algebra (DSeq) TP matmuls in MLP
    manual_attention: bool = False           # manual shard_map SDPA (§Perf A8)
    dp_over_model: bool = False              # pure DP over both axes (§Perf C7)

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return tuple(self.mesh.axis_names)

    @property
    def model_size(self) -> int:
        return self.mesh.shape[self.model_axis]


def moe_init(rng, cfg: ModelConfig) -> Params:
    """The router over all ``n_experts`` and the weights of the experts held
    here.  Each expert's weights are drawn from a key of its own, so a
    share holds exactly what the whole layer holds of those experts."""
    e = cfg.moe
    d, ff = cfg.d_model, e.d_ff_expert
    ks = jax.random.split(rng, 5)
    scale = 1.0 / math.sqrt(d)
    held = slice(e.first_held, e.first_held + e.held)

    def experts(key, shape):
        keys = jax.random.split(key, e.n_experts)[held]
        return jax.vmap(lambda k: jax.random.normal(k, shape, _pdtype(cfg)))(keys)

    p = {
        "router": (jax.random.normal(ks[0], (d, e.n_experts), jnp.float32) * scale),
        "w_gate": experts(ks[1], (d, ff)) * scale,
        "w_up": experts(ks[2], (d, ff)) * scale,
        "w_down": experts(ks[3], (ff, d)) / math.sqrt(ff),
    }
    if e.n_shared_experts:
        sff = ff * e.n_shared_experts
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": dense_init(kk[0], d, sff, cfg),
            "w_up": dense_init(kk[1], d, sff, cfg),
            "w_down": dense_init(kk[2], sff, d, cfg),
        }
    return p


def _route(x_flat: jax.Array, router_w: jax.Array, e):
    """Top-k routing in f32: scores over all experts (softmax or sigmoid),
    the top_k picked, renormalised over the picked ones, times
    ``routed_scale``.  Also the softmax probabilities for the load-balancing
    loss."""
    logits = jnp.matmul(x_flat.astype(jnp.float32), router_w,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    scores = jax.nn.sigmoid(logits) if e.scoring == "sigmoid" else probs
    top_v, top_i = lax.top_k(scores, e.top_k)               # (T, k)
    top_v = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
    return top_i, top_v * e.routed_scale, probs


def _expert_ffn(xs: jax.Array, group_sizes: jax.Array, w_gate, w_up, w_down, dtype):
    """Grouped SwiGLU via ragged_dot.  xs: (C, d) sorted by group."""
    xs = xs.astype(dtype)
    g = lax.ragged_dot(xs, w_gate.astype(dtype), group_sizes)
    u = lax.ragged_dot(xs, w_up.astype(dtype), group_sizes)
    h = (jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u)
    return lax.ragged_dot(h, w_down.astype(dtype), group_sizes)


def _moe_body_ep(x, router_w, w_gate, w_up, w_down, shared, *, cfg: ModelConfig,
                 ep: int, my_shard, fsdp_axes: Tuple[str, ...],
                 model_axis: Optional[str]):
    """Per-device body, expert-sharded layout.  x: (B_loc, S, d) replicated
    over model; expert weights: (E/ep, d[, /fsdp], ff) local shards."""
    e = cfg.moe
    dtype = _dtype(cfg)
    b, s, d = x.shape
    t = b * s
    e_local = e.n_experts // ep
    x_flat = x.reshape(t, d)

    # FSDP: gather the d-sharded expert weights (Table-1 allGatherD)
    for ax in fsdp_axes:
        w_gate = lax.all_gather(w_gate, ax, axis=1, tiled=True)
        w_up = lax.all_gather(w_up, ax, axis=1, tiled=True)
        w_down = lax.all_gather(w_down, ax, axis=2, tiled=True)

    top_i, weights, probs = _route(x_flat, router_w, e)

    # --- local selection: my tokens' assignments to my experts -------------
    cap = int(math.ceil(t * e.top_k / ep * e.capacity_factor))
    cap = max(8, min(cap, t * e.top_k))
    flat_e = top_i.reshape(-1)                               # (T*k,)
    flat_w = weights.reshape(-1)
    is_mine = (flat_e // e_local) == my_shard
    big = t * e.top_k + 1
    pri = jnp.where(is_mine, jnp.arange(t * e.top_k), big)
    order = jnp.argsort(pri)[:cap]                           # first-come capacity
    valid = pri[order] < big
    tok = order // e.top_k
    eid = jnp.where(valid, flat_e[order] - my_shard * e_local, e_local)
    wsel = jnp.where(valid, flat_w[order], 0.0)

    # group by local expert id (stable sort keeps token order within expert)
    g_order = jnp.argsort(eid, stable=True)
    eid_s = eid[g_order]
    tok_s = tok[g_order]
    w_s = wsel[g_order]
    group_sizes = jnp.bincount(eid_s, length=e_local).astype(jnp.int32)

    xs = x_flat[tok_s]                                       # (C, d) gather
    ys = _expert_ffn(xs, group_sizes, w_gate, w_up, w_down, dtype)  # (C, d)
    out = jnp.zeros((t + 1, d), jnp.float32).at[
        jnp.where(eid_s < e_local, tok_s, t)].add(ys.astype(jnp.float32) * w_s[:, None])
    out = out[:t]

    if shared is not None:
        out = out + _shared_ffn(x_flat, shared, cfg, model_axis=None)  # partial added pre-psum
    if model_axis is not None:
        out = lax.psum(out, model_axis)                      # reduceD('sum')
    return out.reshape(b, s, d).astype(dtype), probs


def _shared_ffn(x_flat, shared, cfg, model_axis):
    """Shared expert: dense SwiGLU, ff sharded over model (col→row parallel);
    returns the *partial* (pre-psum) output so it folds into the expert psum."""
    dtype = _dtype(cfg)
    g = jnp.matmul(x_flat.astype(dtype), shared["w_gate"].astype(dtype),
                   preferred_element_type=jnp.float32)
    u = jnp.matmul(x_flat.astype(dtype), shared["w_up"].astype(dtype),
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(dtype)
    return jnp.matmul(h, shared["w_down"].astype(dtype),
                      preferred_element_type=jnp.float32)


def _moe_body_tp(x, router_w, w_gate, w_up, w_down, shared, *, cfg: ModelConfig,
                 fsdp_axes: Tuple[str, ...], model_axis: Optional[str]):
    """ff-sharded layout (expert count < axis size, e.g. Mixtral), and the
    single-device layer: the experts held here (``first_held`` on, as many
    as the weights hold) compute every assignment routed to them (dropless),
    on a d_ff/ep slice; assignments to experts held elsewhere add nothing."""
    e = cfg.moe
    dtype = _dtype(cfg)
    b, s, d = x.shape
    t = b * s
    x_flat = x.reshape(t, d)

    for ax in fsdp_axes:
        w_gate = lax.all_gather(w_gate, ax, axis=1, tiled=True)
        w_up = lax.all_gather(w_up, ax, axis=1, tiled=True)
        w_down = lax.all_gather(w_down, ax, axis=2, tiled=True)

    top_i, weights, probs = _route(x_flat, router_w, e)
    n_held = w_gate.shape[0]
    flat_e = top_i.reshape(-1) - e.first_held
    flat_w = weights.reshape(-1)
    # assignments to experts held elsewhere sort last, past every group
    flat_e = jnp.where((flat_e >= 0) & (flat_e < n_held), flat_e, n_held)
    tok = jnp.arange(t * e.top_k) // e.top_k

    g_order = jnp.argsort(flat_e, stable=True)
    eid_s = flat_e[g_order]
    tok_s = tok[g_order]
    w_s = flat_w[g_order]
    group_sizes = jnp.bincount(eid_s, length=n_held).astype(jnp.int32)

    xs = x_flat[tok_s]
    ys = _expert_ffn(xs, group_sizes, w_gate, w_up, w_down, dtype)
    out = jnp.zeros((t + 1, d), jnp.float32).at[
        jnp.where(eid_s < n_held, tok_s, t)].add(
        ys.astype(jnp.float32) * w_s[:, None])[:t]

    if shared is not None:
        out = out + _shared_ffn(x_flat, shared, cfg, model_axis=None)
    if model_axis is not None:
        out = lax.psum(out, model_axis)
    held = jnp.sum((flat_e < n_held).reshape(t, e.top_k), axis=-1,
                   dtype=jnp.int32)
    return out.reshape(b, s, d).astype(dtype), probs, held.reshape(b, s)


def _moe_body_a2a(x, router_w, w_gate, w_up, w_down, shared, *,
                  cfg: ModelConfig, data_axis: str, model_axis: str,
                  dp: int, my_data_shard):
    """Token-routing EP (FooPar ``allToAllD``): experts are *resident*,
    sharded (E/dp over ``data``) × (ff/tp over ``model``); tokens travel to
    their expert's data-shard via all_to_all, compute with ragged_dot on the
    ff slice, psum the down-projection over ``model``, and a2a back.

    Wire per step ≈ 2·T·k·d·bytes  (tokens move, ~MBs) instead of the
    weight-gathering layout's ≈ E·d·ff·bytes (TBs for 1T-param MoE) — the
    §Perf kimi-decode hillclimb."""
    e = cfg.moe
    dtype = _dtype(cfg)
    b, s, d = x.shape
    t = b * s
    e_local = e.n_experts // dp
    x_flat = x.reshape(t, d)

    top_i, weights, probs = _route(x_flat, router_w, e)
    flat_e = top_i.reshape(-1)                                  # (T*k,)
    flat_w = weights.reshape(-1)
    dest = flat_e // e_local                                    # data shard
    tok = jnp.arange(t * e.top_k) // e.top_k

    # per-destination send buckets (capacity per dest)
    cap = max(8, int(math.ceil(t * e.top_k / dp * e.capacity_factor)))
    order = jnp.argsort(dest * (t * e.top_k) + jnp.arange(t * e.top_k))
    # rank within destination
    big = dp
    onehot_pos = jnp.cumsum(jax.nn.one_hot(dest, dp, dtype=jnp.int32), axis=0)
    slot = onehot_pos[jnp.arange(t * e.top_k), dest] - 1        # 0-based
    valid = slot < cap
    send_x = jnp.zeros((dp, cap, d), dtype)
    send_meta = jnp.full((dp, cap, 3), -1.0, jnp.float32)       # tok, eid, w
    idx = (dest, jnp.where(valid, slot, cap - 1))
    send_x = send_x.at[idx[0], idx[1]].set(
        jnp.where(valid[:, None], x_flat[tok].astype(dtype), send_x[idx[0], idx[1]]))
    send_meta = send_meta.at[idx[0], idx[1]].set(
        jnp.where(valid[:, None],
                  jnp.stack([tok.astype(jnp.float32),
                             (flat_e % e_local).astype(jnp.float32),
                             flat_w], axis=-1),
                  send_meta[idx[0], idx[1]]))

    rx = lax.all_to_all(send_x, data_axis, 0, 0, tiled=True)     # (dp*cap, d)
    rmeta = lax.all_to_all(send_meta, data_axis, 0, 0, tiled=True)
    rx = rx.reshape(dp * cap, d)
    rmeta = rmeta.reshape(dp * cap, 3)
    reid = rmeta[:, 1].astype(jnp.int32)
    rvalid = rmeta[:, 0] >= 0
    reid = jnp.where(rvalid, reid, e_local)

    g_order = jnp.argsort(reid, stable=True)
    xs = rx[g_order]
    group_sizes = jnp.bincount(jnp.where(rvalid, rmeta[:, 1].astype(jnp.int32),
                                         e_local), length=e_local).astype(jnp.int32)
    ys = _expert_ffn(xs, group_sizes, w_gate, w_up, w_down, dtype)  # ff-slice partial
    ys = lax.psum(ys.astype(jnp.float32), model_axis)            # (dp*cap, d)
    # unsort, a2a back to origin shards
    inv = jnp.argsort(g_order)
    back = lax.all_to_all(ys[inv].reshape(dp, cap, d), data_axis, 0, 0,
                          tiled=True).reshape(dp * cap, d)
    bmeta = lax.all_to_all(rmeta.reshape(dp, cap, 3), data_axis, 0, 0,
                           tiled=True).reshape(dp * cap, 3)
    btok = bmeta[:, 0].astype(jnp.int32)
    bw = jnp.where(bmeta[:, 0] >= 0, bmeta[:, 2], 0.0)
    out = jnp.zeros((t + 1, d), jnp.float32).at[
        jnp.where(bmeta[:, 0] >= 0, btok, t)].add(back * bw[:, None])
    out = out[:t]

    if shared is not None:
        out_sh = _shared_ffn(x_flat, shared, cfg, model_axis=None)
        out = out + lax.psum(out_sh, model_axis)
    return out.reshape(b, s, d).astype(dtype), probs


def moe_ffn(p: Params, x: jax.Array, cfg: ModelConfig,
            ctx: Optional[MeshCtx], *, a2a_ep: Optional[bool] = None
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Apply the MoE FFN.  Returns (output, router_probs_for_aux_loss,
    held): ``held`` (B, S) int32 counts each token's top_k experts that are
    held here (all top_k where the layer holds every expert)."""
    e = cfg.moe
    shared = p.get("shared")
    if a2a_ep is None:
        a2a_ep = bool(ctx and ctx.moe_a2a_ep)

    if ctx is None:
        # single-device path (smoke tests): same body, group of 1
        body = partial(_moe_body_tp, cfg=cfg, fsdp_axes=(), model_axis=None)
        return body(x, p["router"], p["w_gate"], p["w_up"], p["w_down"], shared)

    assert e.held == e.n_experts, \
        "a mesh layer holds every expert; a held share runs on one device"
    held = jnp.full(x.shape[:-1], e.top_k, jnp.int32)
    ep = ctx.model_size
    fsdp = tuple(a for a in ctx.fsdp_axes if a != ctx.model_axis)
    use_ep = e.n_experts % ep == 0 and e.n_experts >= ep
    bspec = P(ctx.batch_axes, None, None)

    if a2a_ep and "data" in ctx.batch_axes:
        dp = ctx.mesh.shape["data"]
        assert e.n_experts % dp == 0, (e.n_experts, dp)
        espec_in = P("data", None, ctx.model_axis)      # (E/dp, d, ff/tp)
        espec_out = P("data", ctx.model_axis, None)     # (E/dp, ff/tp, d)
        shared_specs = None
        if shared is not None:
            shared_specs = {"w_gate": P(None, ctx.model_axis),
                            "w_up": P(None, ctx.model_axis),
                            "w_down": P(ctx.model_axis, None)}

        def body(xl, rw, wg, wu, wd, sh):
            return _moe_body_a2a(xl, rw, wg, wu, wd, sh, cfg=cfg,
                                 data_axis="data", model_axis=ctx.model_axis,
                                 dp=dp, my_data_shard=lax.axis_index("data"))

        fn = _shard_map(
            body, mesh=ctx.mesh,
            in_specs=(bspec, P(None, None), espec_in, espec_in, espec_out,
                      shared_specs),
            out_specs=(bspec, P(ctx.batch_axes, None)),
            check=False,
        )
        return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
                  shared) + (held,)

    if use_ep:
        espec_in = P(ctx.model_axis, fsdp if fsdp else None, None)    # (E, d, ff)
        espec_out = P(ctx.model_axis, None, fsdp if fsdp else None)   # (E, ff, d)

        def body(xl, rw, wg, wu, wd, sh):
            return _moe_body_ep(xl, rw, wg, wu, wd, sh, cfg=cfg, ep=ep,
                                my_shard=lax.axis_index(ctx.model_axis),
                                fsdp_axes=fsdp, model_axis=ctx.model_axis)
    else:
        espec_in = P(None, fsdp if fsdp else None, ctx.model_axis)
        espec_out = P(None, ctx.model_axis, fsdp if fsdp else None)

        def body(xl, rw, wg, wu, wd, sh):
            return _moe_body_tp(xl, rw, wg, wu, wd, sh, cfg=cfg,
                                fsdp_axes=fsdp, model_axis=ctx.model_axis)[:2]

    shared_specs = None
    if shared is not None:
        shared_specs = {
            "w_gate": P(None, ctx.model_axis),
            "w_up": P(None, ctx.model_axis),
            "w_down": P(ctx.model_axis, None),
        }

    fn = _shard_map(
        body, mesh=ctx.mesh,
        in_specs=(bspec, P(None, None), espec_in, espec_in, espec_out, shared_specs),
        out_specs=(bspec, P(ctx.batch_axes, None)),
        check=False,
    )
    return fn(x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
              shared) + (held,)


def load_balance_loss(probs: jax.Array, top_i_onehot_mean: Optional[jax.Array] = None) -> jax.Array:
    """Switch-transformer aux loss surrogate: E * mean_e(fraction) * mean_e(prob).
    With only router probs available we use the prob-entropy surrogate."""
    me = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))  # (E,)
    return probs.shape[-1] * jnp.sum(me * me)
