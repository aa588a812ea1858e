"""Sharding plan unit tests (single-device mesh: rules only, no collectives)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import ParallelConfig, SHAPES, get_arch, reduced
from repro.core.hybrid import auto_plan
from repro.core.sharding import ShardingPlan, make_plan
from repro.models import transformer as tf
from repro.launch.mesh import make_host_mesh


def mesh11():
    return make_host_mesh()


@pytest.fixture(scope="module")
def plan():
    return make_plan(mesh11(), ParallelConfig())


def specs_for(arch, plan):
    cfg = get_arch(arch)
    shapes = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    return cfg, shapes, plan.param_specs(cfg, shapes)


def test_dense_param_rules(plan):
    cfg, shapes, specs = specs_for("olmo-1b", plan)
    # embedding: vocab over model
    assert specs["embed"] == P("model", None)
    blk = specs["blocks"]
    # stacked layer dim is unsharded; qkv column-parallel, wo row-parallel
    assert blk["attn"]["wq"] == P(None, None, "model")
    assert blk["attn"]["wo"] == P(None, "model", None)
    assert blk["ffn"]["mlp"]["wi_gate"] == P(None, None, "model")
    assert blk["ffn"]["mlp"]["wo"] == P(None, "model", None)


def test_embed_plan_routes_cf_tables():
    """Top-level table keys named in ``embed_plans`` take their placement
    from the embeddings subsystem (row/col/2D) instead of the LM rules;
    non-dividing tables fall back to replication via the plan guard."""
    from repro.recsys import model as recsys_model
    am = jax.sharding.AbstractMesh((4, 4), ("data", "model"))
    shapes = {"cf_user": jax.ShapeDtypeStruct((64, 8), jnp.float32),
              "cf_item": jax.ShapeDtypeStruct((256, 8), jnp.float32),
              "odd": jax.ShapeDtypeStruct((63, 8), jnp.float32)}
    cfg = get_arch("recllm-base")
    plans = recsys_model.embed_plans("row")
    from repro.embeddings import make_plan as embed_make_plan
    plans["odd"] = embed_make_plan("row")
    sp = ShardingPlan(mesh=am, dp_axes=("data",), tp_axis="model",
                      embed_plans=plans)
    specs = sp.param_specs(cfg, shapes)
    assert specs["cf_user"] == P("model", None)
    assert specs["cf_item"] == P("model", None)
    assert specs["odd"] == P(None, None)        # 63 rows: guard replicates
    # 2D (row x col) placement flows through too
    sp2 = ShardingPlan(mesh=am, dp_axes=("data",), tp_axis="model",
                       embed_plans={"cf_user": embed_make_plan("row_col")})
    assert sp2.param_specs(cfg, shapes)["cf_user"] == P("model", "data")
    # without plans, the tables fall back to replicated LM rules
    sp3 = ShardingPlan(mesh=am, dp_axes=("data",), tp_axis="model")
    assert sp3.param_specs(cfg, shapes)["cf_user"] == P(None, None)


def test_gqa_kv_replication_rule():
    """Production-mesh rules via AbstractMesh (no devices needed)."""
    import dataclasses
    am = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    sp = ShardingPlan(mesh=am, dp_axes=("data",), tp_axis="model")
    # guard: a dim of size 8 cannot shard over 16 — falls back to None
    assert sp.guard(("model",), (8,)) == P(None)
    assert sp.guard(("model",), (16384,)) == P("model")
    cfg = get_arch("internlm2-20b")
    shapes = jax.eval_shape(lambda k: tf.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    specs = sp.param_specs(cfg, shapes)
    blk = specs["blocks"]
    # q heads 48 % 16 == 0 -> sharded; kv 8 < 16 -> replicated (GQA rule)
    assert blk["attn"]["wq"] == P(None, None, "model")
    assert blk["attn"]["wk"] == P(None, None, None)
    assert blk["attn"]["wv"] == P(None, None, None)


def test_moe_expert_rules(plan):
    cfg, shapes, specs = specs_for("qwen3-moe-30b-a3b", plan)
    blk = specs["blocks"]
    assert blk["ffn"]["moe"]["wi_gate"][1] == "model"   # (L, E, d, f)
    assert blk["ffn"]["moe"]["router"] == P(None, None, None)


def test_zero1_adds_dp_axis():
    mesh = make_host_mesh()
    sp = make_plan(mesh, ParallelConfig())
    z = sp.zero1_spec(P(None, "model"), (64, 32))
    assert z == P("data", "model")
    # already dp-sharded: unchanged
    z2 = sp.zero1_spec(P("data", None), (64, 32))
    assert z2 == P("data", None)


def test_constrain_is_noop_without_real_sharding(plan):
    x = jnp.ones((4, 8, 16))
    y = plan.constrain(x, "residual")
    assert y.shape == x.shape


def test_auto_plan_dp_heavy_choice():
    mesh = make_host_mesh()
    # tp=1: dp_heavy not applicable
    plan = auto_plan(get_arch("internlm2-20b"), mesh, SHAPES["train_4k"])
    assert not plan.sharding.dp_heavy
    # moe archs never pick dp_heavy
    plan2 = auto_plan(get_arch("qwen3-moe-30b-a3b"), mesh,
                      SHAPES["train_4k"])
    assert not plan2.sharding.dp_heavy


def test_batch_and_cache_specs(plan):
    cfg = get_arch("olmo-1b")
    batch = {"tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    bs = plan.batch_specs(batch)
    # jax >= 0.5 canonicalizes the singleton dp-axes tuple to its string
    assert bs["tokens"][0] in ("data", ("data",))
    cache = jax.eval_shape(
        lambda: tf.init_cache(reduced(cfg), 8, 32))
    cs = plan.cache_specs(cfg, cache)
    # (L, B, S, Hk, D): batch dim carries the dp axes
    assert cs["k"][1] in ("data", ("data",))
