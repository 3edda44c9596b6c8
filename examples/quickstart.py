"""Quickstart: the whole stack in one page.

  PYTHONPATH=src python examples/quickstart.py

1. build an assigned architecture (reduced config),
2. take two training steps,
3. prefill + decode a few tokens,
4. let the Pond control plane place a "VM" across local/pool memory.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_smoke
from repro.core import compile_cache, traces
from repro.core.control_plane import ControlPlane, ControlPlaneConfig
from repro.core.pool_manager import PoolManager
from repro.data.pipeline import DataConfig, ShardedBatches
from repro.models.model_zoo import build_model
from repro.optim import adamw
from repro.runtime import train as rt
from repro.sharding.rules import ShardCtx


def main():
    compile_cache.enable()
    cfg = get_smoke("qwen2-1.5b")
    model = build_model(cfg)
    print(f"arch={cfg.name}: {cfg.num_layers}L d={cfg.d_model}")

    # --- train two steps ---------------------------------------------------
    ocfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=2, total_steps=10)
    params = model.init_params(jax.random.key(0))
    opt = adamw.init_state(params, ocfg)
    step = rt.jit_train_step(model, ocfg, ShardCtx(), donate=False)
    data = ShardedBatches(DataConfig(cfg.vocab_size, 32, 4))
    for i in range(2):
        batch = {"tokens": jnp.asarray(next(data)["tokens"])}
        params, opt, m = step(params, opt, batch)
        print(f"step {i}: loss={float(m['loss']):.3f}")

    # --- prefill + decode --------------------------------------------------
    toks = jnp.asarray(np.arange(8))[None]
    cache = model.init_cache(1, 32)
    h, cache, _ = jax.jit(lambda p, t, ps, c: model.prefill(p, t, ps, c))(
        params, toks, jnp.arange(8)[None], cache)
    nxt = int(jnp.argmax(model.logits(params, h[:, -1:])[0, -1]))
    outs = [nxt]
    for t in range(8, 12):
        lg, cache = jax.jit(lambda p, t_, ps, c: model.decode(p, t_, ps, c)
                            )(params, jnp.asarray([[nxt]]),
                              jnp.asarray([t]), cache)
        nxt = int(jnp.argmax(lg[0, 0]))
        outs.append(nxt)
    print("generated:", outs)

    # --- Pond placement ----------------------------------------------------
    pop = traces.Population(seed=0)
    vm = pop.sample_vms(1, 60.0, seed=3)[0]
    cp = ControlPlane(ControlPlaneConfig(), None, None,
                      PoolManager(pool_gb=64, buffer_gb=8))
    pl = cp.on_request(vm, host=0, now=0.0)
    print(f"VM {vm.mem_gb:.0f}GB -> local={pl.local_gb:.0f}GB "
          f"pool={pl.pool_gb:.0f}GB")


if __name__ == "__main__":
    main()
