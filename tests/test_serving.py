"""Serving path: batched prefill-into-cache parity + grad accumulation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.config import ParallelConfig, ShapeConfig, TrainConfig, \
    get_arch, reduced
from repro.models import transformer as tf
from repro.models.transformer import ModelCtx
from repro.launch.mesh import make_host_mesh

CTX = ModelCtx(attn_chunk=8)


@pytest.mark.parametrize("name", ["olmo-1b", "whisper-medium"])
def test_prefill_into_cache_matches_teacher_forced_decode(name):
    cfg = dataclasses.replace(reduced(get_arch(name)), dtype="float32")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    B, S_p, S_max = 2, 8, 16
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(3, cfg.vocab_size, (B, S_p)),
                                   jnp.int32)}
    if cfg.encoder_layers:
        batch["frames"] = jnp.asarray(
            rng.normal(0, 0.02, (B, cfg.encoder_frames, cfg.d_model)),
            jnp.float32)
    cache = tf.init_cache(cfg, B, S_max)
    last_logits, cache = tf.prefill_into_cache(cfg, params, batch, cache,
                                               CTX)
    assert int(cache["len"][0]) == S_p

    # decode one more token and compare with running the extended sequence
    nxt = jnp.asarray([[5], [7]], jnp.int32)
    lg, cache = tf.decode_step(cfg, params, cache, nxt, CTX)
    full = {"tokens": jnp.concatenate([batch["tokens"], nxt], 1)}
    if cfg.encoder_layers:
        full["frames"] = batch["frames"]
    logits_full, _, _ = tf.forward(cfg, params, full, CTX)
    assert_allclose(np.asarray(lg[:, 0], np.float32),
                    np.asarray(logits_full[:, -1], np.float32),
                    atol=2e-3, rtol=2e-3)
    # prefill logits themselves match the forward too
    assert_allclose(np.asarray(last_logits, np.float32),
                    np.asarray(tf.forward(cfg, params, batch, CTX)[0][:, -1],
                               np.float32), atol=2e-3, rtol=2e-3)


def test_prefill_unsupported_family_raises():
    cfg = dataclasses.replace(reduced(get_arch("rwkv6-1.6b")),
                              dtype="float32")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    cache = tf.init_cache(cfg, 1, 8)
    with pytest.raises(NotImplementedError):
        tf.prefill_into_cache(cfg, params,
                              {"tokens": jnp.ones((1, 4), jnp.int32)},
                              cache, CTX)


def test_grad_accumulation_matches_monolithic():
    from repro.core.hybrid import auto_plan
    from repro.optimizer import adamw
    from repro.runtime import trainer
    cfg = dataclasses.replace(reduced(get_arch("olmo-1b")), num_layers=2,
                              dtype="float32")
    mesh = make_host_mesh()
    shape = ShapeConfig("t", 16, 8, "train")
    tcfg = TrainConfig(steps=5, checkpoint_every=0, grad_clip=0.0)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(3, 200, (8, 16)), jnp.int32),
             "targets": jnp.asarray(rng.integers(3, 200, (8, 16)),
                                    jnp.int32),
             "mask": jnp.ones((8, 16), jnp.float32)}

    outs = {}
    for micro in (1, 4):
        plan = auto_plan(cfg, mesh, shape,
                         ParallelConfig(microbatches=micro))
        step, jitted, _ = trainer.make_hybrid_train_step(cfg, plan, tcfg)
        params = tf.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw.init_opt_state(params)
        fn = jitted(jax.eval_shape(lambda: params), batch)
        new_p, _, m = fn(params, opt, batch)
        outs[micro] = (m["loss"], new_p)
    assert_allclose(float(outs[1][0]), float(outs[4][0]), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(outs[1][1]), jax.tree.leaves(outs[4][1])):
        assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4)
