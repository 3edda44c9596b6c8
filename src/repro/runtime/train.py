"""Distributed train-step builder.

Layers:
  * ``chunked_xent``     — vocab logits are never materialised for the full
    sequence: lax.map over sequence chunks bounds live memory at
    (B, chunk, V_shard) while keeping the fp32 logsumexp exact.
  * microbatch gradient accumulation (lax.scan) — bounds activation memory;
    with remat this is what lets 398B/671B train shapes fit.
  * ``make_train_step`` — fused step: fwd/bwd + AdamW, params/opt-state
    sharded by sharding/rules.py (FSDP over "data", TP over "model", DP over
    ("pod","data")).
  * ``make_two_phase_steps`` — Pond mode: phase A (device) computes sharded
    grads only; phase B applies the optimizer whose state lives in the pool
    tier.  On TPU phase-B state is ``pinned_host``-backed; on the CPU
    dry-run the split itself is what proves the device working set excludes
    optimizer state (EXPERIMENTS.md §Dry-run).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.compute import einsum_f32
from repro.optim import adamw
from repro.sharding.rules import ShardCtx, default_rules, partition_tree

MTP_WEIGHT = 0.3


def _xent_chunk_stats(h, lab, w):
    """One chunk: (nll_sum, valid_count). Recomputed in fwd AND bwd so the
    (B, chunk, V) logits never outlive a chunk."""
    logits = einsum_f32("bcd,dv->bcv", h, w)
    logz = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(
        logits, jnp.maximum(lab, 0)[..., None], axis=-1)[..., 0]
    valid = lab >= 0
    return jnp.sum(jnp.where(valid, logz - tgt, 0.0)), jnp.sum(valid)


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _xent_core(hc, lc, w):
    """hc: (n, B, c, d); lc: (n, B, c); w: (d, V) -> (nll_sum, count)."""
    sums, counts = jax.lax.map(lambda args: _xent_chunk_stats(
        args[0], args[1], w), (hc, lc))
    return jnp.sum(sums), jnp.sum(counts)


def _xent_core_fwd(hc, lc, w):
    return _xent_core(hc, lc, w), (hc, lc, w)


def _xent_core_bwd(res, cts):
    hc, lc, w = res
    g_sum, _ = cts                                   # d(total)/d(nll_sum)

    def body(dw, args):
        h, lab = args
        logits = einsum_f32("bcd,dv->bcv", h, w)
        p = jax.nn.softmax(logits, axis=-1)
        onehot = jax.nn.one_hot(jnp.maximum(lab, 0), w.shape[1],
                                dtype=jnp.float32)
        dlogit = (p - onehot) * (lab >= 0)[..., None] * g_sum
        dh = jnp.einsum("bcv,dv->bcd", dlogit.astype(w.dtype), w)
        dw = dw + einsum_f32("bcd,bcv->dv", h, dlogit.astype(h.dtype))
        return dw, dh

    dw0 = jnp.zeros(w.shape, jnp.float32)
    dw, dhc = jax.lax.scan(body, dw0, (hc, lc))
    f0 = np.zeros(lc.shape, jax.dtypes.float0)
    return dhc.astype(hc.dtype), f0, dw.astype(w.dtype)


_xent_core.defvjp(_xent_core_fwd, _xent_core_bwd)


def chunked_xent(hidden, w, labels, chunk: int = 512,
                 ctx: ShardCtx | None = None):
    """Mean token NLL.  hidden: (B,S,d); w: (d,V); labels: (B,S) int32.

    Custom VJP: without it, lax.map's backward stores every chunk's
    (B, chunk, V) fp32 logits = the full logits tensor (~10 GB/device for
    152k vocab at 4k seq) — the exact memory wall chunking exists to avoid.
    """
    b, s, d = hidden.shape
    c = min(chunk, s)
    pad = (-s) % c
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    n = (s + pad) // c
    hc = jnp.moveaxis(hidden.reshape(b, n, c, d), 1, 0)
    lc = jnp.moveaxis(labels.reshape(b, n, c), 1, 0)
    if (ctx is not None and ctx.mesh is not None and ctx.replicate_lm_head
            and c % ctx.mesh.shape[ctx.model_axis] == 0):
        # tied-head mode: the vocab dim is unshardable, so shard the chunk
        # tokens over the model axis instead — the (B, c/TP, V) logits
        # matmul splits 16-ways with only scalar psums.  shard_map (not a
        # constraint): SPMD propagation re-replicates a bare constraint
        # through the scan (measured, EXPERIMENTS §Perf B2).
        ma = ctx.model_axis

        def local(hc_l, lc_l, w_l):
            tot, cnt = _xent_core(hc_l, lc_l, w_l)
            return (jax.lax.psum(tot, ma),
                    jax.lax.psum(cnt, ma))

        total, count = jax.shard_map(
            local, mesh=ctx.mesh,
            in_specs=(P(None, None, ma, None), P(None, None, ma),
                      P(None, None)),
            out_specs=(P(), P()), check_vma=False)(hc, lc, w)
        return total / jnp.maximum(count, 1)
    total, count = _xent_core(hc, lc, w)
    return total / jnp.maximum(count, 1)


def loss_fn(model, params, batch, ctx: ShardCtx, xent_chunk: int = 512):
    """batch: {"tokens": (B, S+1)[, "embeds": (B, N, d)]}."""
    tokens = batch["tokens"]
    inp, labels = tokens[:, :-1], tokens[:, 1:]
    embeds = batch.get("embeds")
    is_encdec = getattr(model.cfg, "is_encoder_decoder", False)
    # enc-dec: embeds feed the encoder, not the decoder prefix
    n_emb = 0 if embeds is None or is_encdec else embeds.shape[1]
    s = inp.shape[1] + n_emb
    positions = jnp.broadcast_to(jnp.arange(s)[None], (inp.shape[0], s))
    out = model.forward(params, inp, positions, ctx, embeds=embeds)
    hidden = out["hidden"][:, n_emb:]          # frontend tokens carry no loss
    w = model.lm_head_weight(params)
    loss = chunked_xent(hidden, w, labels, xent_chunk, ctx)
    total = loss + out["aux"]
    if "mtp_hidden" in out:                     # predict t+2 (DeepSeek MTP)
        mtp_loss = chunked_xent(out["mtp_hidden"][:, : -1],
                                w, labels[:, 2:], xent_chunk, ctx)
        total = total + MTP_WEIGHT * mtp_loss
    return total, {"loss": loss, "aux": out["aux"]}


def grads_fn(model, params, batch, ctx: ShardCtx, microbatches: int = 1,
             xent_chunk: int = 512, accum_dtype=jnp.float32):
    """Sharded grads with lax.scan microbatch accumulation.

    accum_dtype: fp32 by default; the 398B/671B train shapes use bf16
    accumulation so the grad buffer stays at param size (EXPERIMENTS.md
    §Dry-run discusses the trade-off)."""
    vg = jax.value_and_grad(
        lambda p, b: loss_fn(model, p, b, ctx, xent_chunk), has_aux=True)
    if microbatches == 1:
        (_, metrics), grads = vg(params, batch)
        return grads, metrics

    def split(x):
        bsz = x.shape[0]
        assert bsz % microbatches == 0, (bsz, microbatches)
        r = x.reshape((microbatches, bsz // microbatches) + x.shape[1:])
        # keep the per-microbatch slice sharded over the batch axes
        return ctx.constrain(
            r, P(None, ctx.batch_axes, *([None] * (x.ndim - 1))))

    mb = jax.tree.map(split, batch)
    zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, accum_dtype), params)

    def body(carry, b):
        g_acc, loss_acc = carry
        (_, metrics), g = vg(params, b)
        g_acc = jax.tree.map(lambda a, x: a + x.astype(accum_dtype),
                             g_acc, g)
        return (g_acc, loss_acc + metrics["loss"]), None

    (g, loss_sum), _ = jax.lax.scan(body, (zero_g, jnp.zeros(())), mb)
    g = jax.tree.map(lambda x: x / microbatches, g)
    return g, {"loss": loss_sum / microbatches,
               "aux": jnp.zeros(())}


# ------------------------------------------------------------ step builders
def make_train_step(model, opt_cfg: adamw.AdamWConfig, ctx: ShardCtx,
                    microbatches: int = 1, xent_chunk: int = 512,
                    accum_dtype=jnp.float32):
    """Fused step: (params, opt_state, batch) -> (params, opt_state, metrics)."""
    def step(params, opt_state, batch):
        grads, metrics = grads_fn(model, params, batch, ctx, microbatches,
                                  xent_chunk, accum_dtype)
        params, opt_state, om = adamw.apply_updates(params, opt_state,
                                                    grads, opt_cfg)
        return params, opt_state, {**metrics, **om}
    return step


def make_two_phase_steps(model, opt_cfg: adamw.AdamWConfig, ctx: ShardCtx,
                         microbatches: int = 1, xent_chunk: int = 512,
                         accum_dtype=jnp.float32):
    """Pond split: grad_step stays on device; opt_step streams pool state."""
    def grad_step(params, batch):
        return grads_fn(model, params, batch, ctx, microbatches, xent_chunk,
                        accum_dtype)

    def opt_step(params, opt_state, grads):
        return adamw.apply_updates(params, opt_state, grads, opt_cfg)
    return grad_step, opt_step


def jit_train_step(model, opt_cfg, ctx: ShardCtx, *, mode: str = "train",
                   microbatches: int = 1, xent_chunk: int = 512,
                   donate: bool = True, accum_dtype=jnp.float32):
    """jit with in/out shardings derived from the rules table."""
    step = make_train_step(model, opt_cfg, ctx, microbatches, xent_chunk,
                           accum_dtype)
    if ctx.mesh is None:
        return jax.jit(step, donate_argnums=(0, 1) if donate else ())
    if opt_cfg.moments_dtype == "int8":
        raise ValueError("int8 moments are a pool-tier feature: use "
                         "make_two_phase_steps (opt state streams from the "
                         "pool tier, shardings inferred from buffers)")
    params_sh, opt_sh, batch_sh = step_shardings(model, opt_cfg, ctx, mode)
    return jax.jit(step,
                   in_shardings=(params_sh, opt_sh, batch_sh),
                   out_shardings=(params_sh, opt_sh, None),
                   donate_argnums=(0, 1) if donate else ())


def step_shardings(model, opt_cfg, ctx: ShardCtx, mode: str = "train"):
    """(params, opt_state, batch) NamedSharding trees for the fused step."""
    rules = default_rules(ctx, mode=mode)
    pspec = partition_tree(model.specs(), rules, ctx.mesh)
    params_sh = jax.tree.map(lambda s: NamedSharding(ctx.mesh, s), pspec)
    opt_sh = {
        "step": NamedSharding(ctx.mesh, P()),
        "master": params_sh if opt_cfg.master_fp32 else None,
        "m": params_sh,
        "v": params_sh,
    }
    batch_sh = {"tokens": NamedSharding(ctx.mesh, P(ctx.batch_axes, None))}
    return params_sh, opt_sh, batch_sh
