"""Hybrid-parallelism demo (paper §III): the same model trained under four
gradient-sync regimes on an 8-device host mesh — flat ring All-Reduce
(Eq. 8), hierarchical All-Reduce (rack->pod analogue), 1-bit EF-signSGD
(Eq. 10), and top-k sparsification (Eq. 11) — printing loss curves and the
per-step wire bytes each scheme puts on the interconnect.

  PYTHONPATH=src python examples/hybrid_parallel_demo.py
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"   # virtual host devices, never a chip

import dataclasses  # noqa: E402

import jax  # noqa: E402
from jax.sharding import AxisType  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import TrainConfig, get_arch, reduced  # noqa: E402
from repro.data import pipeline  # noqa: E402
from repro.models import transformer as tf  # noqa: E402
from repro.models.transformer import ModelCtx  # noqa: E402
from repro.optimizer import adamw  # noqa: E402
from repro.runtime import trainer  # noqa: E402


def main():
    cfg = dataclasses.replace(reduced(get_arch("recllm-base")),
                              dtype="float32")
    ctx = ModelCtx(attn_chunk=8)
    mesh = jax.make_mesh((2, 4), ("pod", "data"),
                         axis_types=(AxisType.Auto,) * 2)
    tcfg = TrainConfig(steps=30, learning_rate=3e-3, warmup_steps=3,
                       checkpoint_every=0)

    def loss_fn(p, b):
        return tf.loss_fn(cfg, p, b, ctx)[0]

    data = [{k: jnp.asarray(v) for k, v in b.items()}
            for b in pipeline.synthetic_lm_batches(cfg.vocab_size, 32, 16,
                                                   30, seed=5)]
    n_params = sum(x.size for x in jax.tree.leaves(
        tf.init_params(jax.random.PRNGKey(0), cfg)))

    print(f"model: recllm reduced, {n_params:,} params; "
          f"mesh pod=2 x data=4\n")
    print(f"{'sync mode':16s} {'final loss':>10s} {'wire bytes/step':>16s}")
    for mode, inter in (("flat", None), ("hierarchical", "pod"),
                        ("onebit", None), ("topk", None)):
        scfg = trainer.DPSyncConfig(mode=mode, inter_axis=inter,
                                    block=512, topk_block=2048, k=64)
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw.init_opt_state(params)
        n = trainer.residual_size(params, scfg)
        resid = jnp.zeros((8, n))
        step = trainer.make_dp_train_step(loss_fn, mesh, tcfg, scfg)
        losses = []
        for b in data:
            params, opt, resid, loss = step(params, opt, resid, b)
            losses.append(float(loss))
        if mode == "flat":
            wire = 2 * n_params * 4
        elif mode == "hierarchical":
            wire = n_params * 4 * (1 + 2 / 4)   # RS + cross-pod AR + AG
        elif mode == "onebit":
            wire = n // 8 + n // 512 * 4        # packed signs + scales
        else:
            wire = n // 2048 * 64 * 8           # (val, idx) x k per block
        print(f"{mode:16s} {losses[-1]:10.4f} {wire:16,}")
    print("\ncompression cuts wire bytes ~8-30x at equal convergence "
          "(paper §III.B).")


if __name__ == "__main__":
    main()
