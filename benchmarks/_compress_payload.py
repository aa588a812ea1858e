"""Subprocess payload: compression ablation on real 8-way DP training of the
RecLLM recommender — reproduces the paper's claim that 1-bit / top-k
gradient compression does not degrade HR@10 / NDCG@10 (§III.B, Table 2).
"""
import json
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"   # virtual host devices, never a chip

import dataclasses  # noqa: E402

import jax  # noqa: E402
from jax.sharding import AxisType  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.config import TrainConfig, get_arch, reduced  # noqa: E402
from repro.models.transformer import ModelCtx  # noqa: E402
from repro.optimizer import adamw  # noqa: E402
from repro.recsys import dataset, metrics, model as recmodel  # noqa: E402
from repro.runtime import trainer  # noqa: E402

ds = dataset.generate(scale=0.005, seed=0)
cfg = dataclasses.replace(reduced(get_arch("recllm-base")),
                          vocab_size=ds.n_items + 3, vocab_pad_to=32,
                          dtype="float32")
ctx = ModelCtx(attn_chunk=8)
mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
STEPS = 50


def loss_fn(p, b):
    return recmodel.recllm_loss(cfg, p, b, ctx)[0]


toks, gold, lens = dataset.eval_examples(ds, seq_len=16, max_users=128)
users = jnp.zeros((toks.shape[0],), jnp.int32)


def eval_hr(p):
    scores = recmodel.score_users(cfg, p, jnp.asarray(toks), users,
                                  jnp.asarray(lens), ctx)
    hr, ndcg = metrics.hr_ndcg_at_k(scores, jnp.asarray(gold), k=10)
    return float(hr), float(ndcg)


out = {}
N_PARAMS = None
for mode in ("flat", "hierarchical", "onebit", "topk"):
    params = recmodel.init_recllm(jax.random.PRNGKey(0), cfg, ds.n_users)
    opt = adamw.init_opt_state(params)
    tcfg = TrainConfig(steps=STEPS, learning_rate=1e-2, warmup_steps=5,
                       weight_decay=0.0, grad_clip=1.0, checkpoint_every=0)
    scfg = trainer.DPSyncConfig(
        mode=mode, block=512, topk_block=2048, k=64,
        inter_axis=None)
    n = trainer.residual_size(params, scfg)
    resid = jnp.zeros((8, n))
    step = trainer.make_dp_train_step(loss_fn, mesh, tcfg, scfg)
    N_PARAMS = sum(x.size for x in jax.tree.leaves(params))

    losses = []
    for batch in dataset.seq_batches(ds, 32, 16, steps=STEPS, seed=7):
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt, resid, loss = step(params, opt, resid, batch)
        losses.append(float(loss))
    hr, ndcg = eval_hr(params)
    # wire bytes per step per rank (analytic, from the sync contract)
    if mode in ("flat", "hierarchical"):
        wire = N_PARAMS * 4 * (2 if mode == "flat" else 1)
    elif mode == "onebit":
        wire = n // 8 + (n // 512) * 4
    else:
        wire = (n // 2048) * 64 * 8
    out[mode] = {"final_loss": float(np.mean(losses[-5:])),
                 "first_loss": losses[0], "hr10": hr, "ndcg10": ndcg,
                 "wire_bytes": wire}

print("BENCH_JSON:" + json.dumps(out))
