"""Mixture-of-Experts layer: top-k router, shared experts, two dispatch paths.

* ``moe_dense``    — reference path: computes every expert for every token and
  masks by routing weights.  Exact (no capacity drops); used for smoke tests
  and as the oracle for the sharded path.
* ``moe_sharded``  — production path: ``shard_map`` over the EP (= model) mesh
  axis.  Tokens are replicated across EP ranks (they already are under our
  TP sharding); each rank scatters the tokens routed to *its* experts into an
  (E_local, C, d) buffer (sort-based position-in-expert, capacity drops),
  runs the grouped expert FFN, scatter-adds back, and one ``psum`` over the
  EP axis combines contributions.  Collectives: FSDP all-gather of expert
  weights (inserted at the shard_map boundary) + one psum of (T, d).

Aux losses (load-balance + router z-loss) are computed outside the
shard_map from a cheap recomputation of router logits so they stay exact
under pjit without cross-shard plumbing.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.params import ParamSpec


# ----------------------------------------------------------------- specs ---
def moe_specs(cfg: ArchConfig, prefix_axes=()) -> dict:
    m = cfg.moe
    d = cfg.d_model
    ff = m.d_ff_expert or cfg.d_ff
    pa = prefix_axes
    sp = {
        "router": ParamSpec((d, m.num_experts), jnp.float32,
                            pa + ("embed", None)),
        "w_gate": ParamSpec((m.num_experts, d, ff), jnp.bfloat16,
                            pa + ("experts", "embed", "expert_ff")),
        "w_up": ParamSpec((m.num_experts, d, ff), jnp.bfloat16,
                          pa + ("experts", "embed", "expert_ff")),
        "w_down": ParamSpec((m.num_experts, ff, d), jnp.bfloat16,
                            pa + ("experts", "expert_ff", "embed")),
    }
    if m.num_shared_experts:
        sff = ff * m.num_shared_experts
        sp["shared"] = {
            "wi_gate": ParamSpec((d, sff), jnp.bfloat16, pa + ("embed", "ff")),
            "wi_up": ParamSpec((d, sff), jnp.bfloat16, pa + ("embed", "ff")),
            "wo": ParamSpec((sff, d), jnp.bfloat16, pa + ("ff", "embed")),
        }
    return sp


# ---------------------------------------------------------------- routing --
def router_topk(logits: jax.Array, k: int):
    """logits: (T, E) fp32 -> (gates (T,k) fp32 normalized, idx (T,k) i32)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    return gates, idx


def aux_losses(logits: jax.Array, idx: jax.Array, num_experts: int,
               aux_w: float, z_w: float) -> jax.Array:
    """Load-balance + z loss (scalar, fp32). logits: (T,E); idx: (T,k)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    pe = jnp.mean(probs, axis=0)                              # (E,)
    onehot = jax.nn.one_hot(idx, num_experts, dtype=jnp.float32)
    fe = jnp.mean(jnp.sum(onehot, axis=1), axis=0)            # (E,)
    lb = num_experts * jnp.sum(pe * fe)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return aux_w * lb + z_w * z


def _expert_ffn(w_gate, w_up, w_down, x):
    """Grouped FFN. x: (E, C, d) -> (E, C, d)."""
    g = jnp.einsum("ecd,edf->ecf", x, w_gate)
    u = jnp.einsum("ecd,edf->ecf", x, w_up)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, w_down)


def _shared_ffn(p, x):
    g = jnp.einsum("bsd,df->bsf", x, p["wi_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["wi_up"])
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["wo"])


# ------------------------------------------------------------- dense path --
def moe_dense(p: dict, x: jax.Array, cfg: ArchConfig):
    """Exact reference: all experts on all tokens. x: (B,S,d)."""
    m = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    gates, idx = router_topk(logits, m.top_k)
    dense_w = jnp.zeros((b * s, m.num_experts), jnp.float32)
    dense_w = jax.vmap(lambda w, i, g: w.at[i].add(g))(dense_w, idx, gates)
    eo = _expert_ffn(p["w_gate"], p["w_up"], p["w_down"],
                     jnp.broadcast_to(xt, (m.num_experts, b * s, d)))
    y = jnp.einsum("etd,te->td", eo.astype(jnp.float32), dense_w)
    y = y.astype(x.dtype).reshape(b, s, d)
    if m.num_shared_experts:
        y = y + _shared_ffn(p["shared"], x)
    aux = aux_losses(logits, idx, m.num_experts, m.aux_loss, m.router_z_loss)
    return y, aux


# ----------------------------------------------------------- sharded path --
def _positions_in_expert(e_flat: jax.Array, num_experts: int):
    """Sort-based position-in-expert (stable).  e_flat: (Tk,) int32."""
    tk = e_flat.shape[0]
    sort_idx = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[sort_idx]
    counts = jnp.bincount(e_flat, length=num_experts)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    pos_sorted = jnp.arange(tk, dtype=jnp.int32) - starts[e_sorted].astype(
        jnp.int32)
    return jnp.zeros((tk,), jnp.int32).at[sort_idx].set(pos_sorted)


def _batch_axes_for(ctx, b: int) -> tuple:
    """Largest prefix of ctx.batch_axes whose product divides b."""
    axes = []
    n = 1
    for a in ctx.batch_axes:
        if b % (n * ctx.mesh.shape[a]) == 0:
            axes.append(a)
            n *= ctx.mesh.shape[a]
    return tuple(axes)


def moe_sharded(p: dict, x: jax.Array, cfg: ArchConfig, ctx,
                capacity_factor: float | None = None):
    """shard_map EP dispatch.  ctx: ShardCtx (sharding/rules.py)."""
    m = cfg.moe
    b, s, d = x.shape
    ep = ctx.mesh.shape[ctx.model_axis]
    assert m.num_experts % ep == 0, (m.num_experts, ep)
    el = m.num_experts // ep
    cf = capacity_factor if capacity_factor is not None else m.capacity_factor

    # aux losses from a cheap pjit-level recomputation (exact, global mean)
    logits_g = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    _, idx_g = router_topk(logits_g.reshape(b * s, -1), m.top_k)
    aux = aux_losses(logits_g.reshape(b * s, -1), idx_g, m.num_experts,
                     m.aux_loss, m.router_z_loss)

    batch_axes = _batch_axes_for(ctx, b)
    batch_spec = P(batch_axes if batch_axes else None, None, None)
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= ctx.mesh.shape[a]
    t_local = (b // n_batch_shards) * s
    cap = max(8, int(t_local * m.top_k * cf / m.num_experts))

    def local_fn(xl, wr, wg, wu, wd):
        bl, sl, _ = xl.shape
        t = bl * sl
        xt = xl.reshape(t, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), wr)
        gates, idx = router_topk(logits, m.top_k)            # (t,k)
        rank = jax.lax.axis_index(ctx.model_axis)
        e_flat = idx.reshape(-1)                             # (t*k,)
        pos = _positions_in_expert(e_flat, m.num_experts)
        mine = (e_flat // el) == rank
        keep = mine & (pos < cap)
        slot = jnp.where(keep, (e_flat % el) * cap + pos, el * cap)
        buf = jnp.zeros((el * cap + 1, d), xt.dtype)
        tok_of = jnp.repeat(jnp.arange(t, dtype=jnp.int32), m.top_k)
        buf = buf.at[slot].add(xt[tok_of], mode="drop")
        eo = _expert_ffn(wg, wu, wd, buf[:-1].reshape(el, cap, d))
        eo = eo.reshape(el * cap, d)
        g_flat = gates.reshape(-1).astype(jnp.float32)
        contrib = (eo[jnp.minimum(slot, el * cap - 1)].astype(jnp.float32)
                   * (g_flat * keep)[:, None])
        y = jnp.zeros((t, d), jnp.float32).at[tok_of].add(contrib)
        y = jax.lax.psum(y, ctx.model_axis)
        return y.astype(xl.dtype).reshape(bl, sl, d)

    y = jax.shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(batch_spec, P(None, None), P(ctx.model_axis, None, None),
                  P(ctx.model_axis, None, None), P(ctx.model_axis, None, None)),
        out_specs=batch_spec,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if m.num_shared_experts:
        y = y + _shared_ffn(p["shared"], x)
    return y, aux


def moe_sharded_2d(p: dict, x: jax.Array, cfg: ArchConfig, ctx,
                   capacity_factor: float | None = None):
    """Serve-scale EP: experts over "model" AND expert-ffn over "data"
    (DeepSeek-V3 serves with EP spanning the full slice — 671B/398B expert
    weights cannot live on a 16-way TP shard).

    Dataflow per (data, model) device:
      all-gather tokens over "data" -> route -> scatter into the local
      (E/model, C) buffer -> grouped FFN on the local ff shard ->
      scatter-add token contributions -> reduce-scatter over "data"
      (returns each data-rank its own tokens, summed over ff shards) ->
      psum over "model" (sums expert groups).
    """
    m = cfg.moe
    b, s, d = x.shape
    ep = ctx.mesh.shape[ctx.model_axis]
    ff = m.d_ff_expert or cfg.d_ff
    assert m.num_experts % ep == 0
    assert ff % ctx.mesh.shape[ctx.data_axis] == 0
    el = m.num_experts // ep
    cf = capacity_factor if capacity_factor is not None else m.capacity_factor

    logits_g = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    _, idx_g = router_topk(logits_g.reshape(b * s, -1), m.top_k)
    aux = aux_losses(logits_g.reshape(b * s, -1), idx_g, m.num_experts,
                     m.aux_loss, m.router_z_loss)

    da = ctx.data_axis
    batch_axes = _batch_axes_for(ctx, b)
    gather_data = da in batch_axes
    batch_spec = P(batch_axes if batch_axes else None, None, None)
    n_pod = 1
    for a in batch_axes:
        if a != da:
            n_pod *= ctx.mesh.shape[a]
    t_g = (b // n_pod) * s                       # tokens after data-gather
    cap = max(8, int(t_g * m.top_k * cf / m.num_experts))

    def local_fn(xl, wr, wg, wu, wd):
        if gather_data:
            xl = jax.lax.all_gather(xl, da, axis=0, tiled=True)
        xt = xl.reshape(-1, d)
        t = xt.shape[0]
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), wr)
        gates, idx = router_topk(logits, m.top_k)
        rank = jax.lax.axis_index(ctx.model_axis)
        e_flat = idx.reshape(-1)
        pos = _positions_in_expert(e_flat, m.num_experts)
        keep = ((e_flat // el) == rank) & (pos < cap)
        slot = jnp.where(keep, (e_flat % el) * cap + pos, el * cap)
        buf = jnp.zeros((el * cap + 1, d), xt.dtype)
        tok_of = jnp.repeat(jnp.arange(t, dtype=jnp.int32), m.top_k)
        buf = buf.at[slot].add(xt[tok_of], mode="drop")
        eo = _expert_ffn(wg, wu, wd, buf[:-1].reshape(el, cap, d))
        eo = eo.reshape(el * cap, d)
        g_flat = gates.reshape(-1).astype(jnp.float32)
        contrib = (eo[jnp.minimum(slot, el * cap - 1)].astype(jnp.float32)
                   * (g_flat * keep)[:, None])
        y = jnp.zeros((t, d), jnp.float32).at[tok_of].add(contrib)
        if gather_data:
            # returns each data-rank its own tokens, summing ff partials
            y = jax.lax.psum_scatter(y, da, scatter_dimension=0, tiled=True)
            bl = b // (n_pod * ctx.mesh.shape[da])
        else:
            y = jax.lax.psum(y, da)              # ff partials only
            bl = b // n_pod
        y = jax.lax.psum(y, ctx.model_axis)      # expert groups
        return y.astype(xl.dtype).reshape(bl, s, d)

    y = jax.shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(batch_spec, P(None, None),
                  P(ctx.model_axis, None, da),
                  P(ctx.model_axis, None, da),
                  P(ctx.model_axis, da, None)),
        out_specs=batch_spec,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if m.num_shared_experts:
        y = y + _shared_ffn(p["shared"], x)
    return y, aux


def moe_sharded_a2a(p: dict, x: jax.Array, cfg: ArchConfig, ctx,
                    capacity_factor: float | None = None):
    """Token-routed EP over the combined ("data","model") axes: each device
    owns E/(data*model) experts and tokens travel by all-to-all instead of
    gather+reduce-scatter.  Wire per device ~= 2 x T_local x top_k x cf x d
    (bf16), vs ~2 x T_gathered x d for the gather scheme — the deepseek
    prefill hillclimb (EXPERIMENTS.md §Perf).
    """
    m = cfg.moe
    b, s, d = x.shape
    da, ma = ctx.data_axis, ctx.model_axis
    n_ep = ctx.mesh.shape[da] * ctx.mesh.shape[ma]
    assert m.num_experts % n_ep == 0, (m.num_experts, n_ep)
    el = m.num_experts // n_ep
    cf = capacity_factor if capacity_factor is not None else m.capacity_factor

    logits_g = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    _, idx_g = router_topk(logits_g.reshape(b * s, -1), m.top_k)
    aux = aux_losses(logits_g.reshape(b * s, -1), idx_g, m.num_experts,
                     m.aux_loss, m.router_z_loss)

    msize = ctx.mesh.shape[ma]
    if s % msize or s == 1:
        return moe_sharded_2d(p, x, cfg, ctx, capacity_factor)
    batch_axes = _batch_axes_for(ctx, b)
    # tokens fully sharded: batch over (pod, data), SEQUENCE over model —
    # every device owns a distinct token set, no duplicated routing
    batch_spec = P(batch_axes if batch_axes else None, ma, None)
    n_shards = 1
    for a in batch_axes:
        n_shards *= ctx.mesh.shape[a]
    t_loc = (b // n_shards) * (s // msize)
    cap = max(8, int(t_loc * m.top_k * cf / n_ep))   # per (src,dst) pair

    def local_fn(xl, wr, wg, wu, wd):
        bl, sl, _ = xl.shape
        t = bl * sl
        xt = xl.reshape(t, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), wr)
        gates, idx = router_topk(logits, m.top_k)
        e_flat = idx.reshape(-1)
        dest = e_flat // el                               # owner device
        pos = _positions_in_expert(dest, n_ep)            # slot at dest
        keep = pos < cap
        slot = jnp.where(keep, dest * cap + pos, n_ep * cap)
        tok_of = jnp.repeat(jnp.arange(t, dtype=jnp.int32), m.top_k)
        send_x = jnp.zeros((n_ep * cap + 1, d), xt.dtype)
        send_x = send_x.at[slot].set(xt[tok_of], mode="drop")
        send_le = jnp.full((n_ep * cap + 1,), el, jnp.int32)  # pad expert
        send_le = send_le.at[slot].set(e_flat % el, mode="drop")
        # route tokens to expert owners (payload: bf16 activations + ids)
        recv_x = jax.lax.all_to_all(send_x[:-1].reshape(n_ep, cap, d),
                                    (da, ma), 0, 0, tiled=False)
        recv_le = jax.lax.all_to_all(send_le[:-1].reshape(n_ep, cap),
                                     (da, ma), 0, 0, tiled=False)
        recv_x = recv_x.reshape(n_ep * cap, d)
        recv_le = recv_le.reshape(n_ep * cap)
        # grouped FFN over owned experts (one-hot select; el is small)
        onehot = jax.nn.one_hot(recv_le, el, dtype=recv_x.dtype)
        xg = jnp.einsum("td,te->etd", recv_x, onehot)
        yg = _expert_ffn(wg, wu, wd, xg)
        y_tok = jnp.einsum("etd,te->td", yg, onehot)
        # send results back to the token owners
        back = jax.lax.all_to_all(y_tok.reshape(n_ep, cap, d),
                                  (da, ma), 0, 0, tiled=False)
        back = back.reshape(n_ep * cap, d)
        g_flat = gates.reshape(-1).astype(jnp.float32)
        contrib = (back[jnp.minimum(slot, n_ep * cap - 1)]
                   .astype(jnp.float32) * (g_flat * keep)[:, None])
        y = jnp.zeros((t, d), jnp.float32).at[tok_of].add(contrib)
        return y.astype(xl.dtype).reshape(bl, sl, d)

    y = jax.shard_map(
        local_fn, mesh=ctx.mesh,
        in_specs=(batch_spec, P(None, None),
                  P((da, ma), None, None), P((da, ma), None, None),
                  P((da, ma), None, None)),
        out_specs=batch_spec, check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if m.num_shared_experts:
        y = y + _shared_ffn(p["shared"], x)
    return y, aux


def apply_moe(p: dict, x: jax.Array, cfg: ArchConfig, ctx=None,
              capacity_factor: float | None = None):
    """Dispatch on context: sharded when a mesh with EP-divisible experts is
    present, dense reference otherwise."""
    if (ctx is not None and ctx.mesh is not None
            and cfg.moe.num_experts % ctx.mesh.shape[ctx.model_axis] == 0
            and ctx.moe_impl != "dense"):
        if ctx.moe_impl == "sharded2d":
            return moe_sharded_2d(p, x, cfg, ctx, capacity_factor)
        if ctx.moe_impl == "sharded_a2a":
            return moe_sharded_a2a(p, x, cfg, ctx, capacity_factor)
        return moe_sharded(p, x, cfg, ctx, capacity_factor)
    return moe_dense(p, x, cfg)
