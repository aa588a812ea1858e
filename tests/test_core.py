"""Unit tests for the paper-core algorithms that need no multi-device mesh:
load balancing (C4), async delay compensation (C7), the hybrid planner (C8),
and straggler mitigation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.config import SHAPES, ParallelConfig, get_arch
from repro.core import async_dp, hybrid, load_balance as lb
from repro.runtime import straggler
from repro.launch.mesh import make_host_mesh


# -- expert rebalancing (LPT) -------------------------------------------------

def test_rebalance_experts_improves_balance():
    rng = np.random.default_rng(0)
    load = rng.pareto(1.5, 64) + 0.1
    assign, perm = lb.rebalance_experts(load, 8)
    q = lb.balance_quality(load, assign, 8)
    naive = lb.balance_quality(load, np.arange(64) // 8, 8)
    lower = load.max() / (load.sum() / 8)
    assert q <= naive
    assert q <= max(1.0, lower) * 1.2
    # capacity respected, permutation valid
    assert (np.bincount(assign, minlength=8) == 8).all()
    assert sorted(perm) == list(range(64))


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([2, 4, 8, 16]))
def test_rebalance_property(seed, n_dev):
    rng = np.random.default_rng(seed)
    E = n_dev * rng.integers(1, 9)
    load = rng.exponential(1.0, E) + 1e-3
    assign, perm = lb.rebalance_experts(load, n_dev)
    assert (np.bincount(assign, minlength=n_dev) == E // n_dev).all()
    naive = lb.balance_quality(load, np.arange(E) % n_dev, n_dev)
    assert lb.balance_quality(load, assign, n_dev) <= naive + 1e-9


# -- pipeline stage balancing --------------------------------------------------

def test_balance_stages_optimal_on_known_case():
    costs = [1, 1, 1, 1, 10, 1, 1, 1]
    b = lb.balance_stages(costs, 2)
    sc = lb.stage_costs(costs, b)
    # brute-force optimum over all single cuts
    best = min(max(sum(costs[:i]), sum(costs[i:])) for i in range(1, 8))
    assert sc.max() == best == 13.0
    assert b[0] == 0 and b[-1] == 8


@settings(max_examples=30, deadline=None)
@given(st.lists(st.floats(0.1, 10), min_size=4, max_size=24),
       st.integers(2, 4))
def test_balance_stages_beats_uniform(costs, n_stages):
    if len(costs) < n_stages:
        return
    b = lb.balance_stages(costs, n_stages)
    opt = lb.stage_costs(costs, b).max()
    L = len(costs)
    uni = [round(i * L / n_stages) for i in range(n_stages + 1)]
    uni_cost = max(sum(costs[uni[s]:uni[s + 1]]) for s in range(n_stages))
    assert opt <= uni_cost + 1e-9
    # contiguity + coverage
    assert b[0] == 0 and b[-1] == L and all(x <= y for x, y in zip(b, b[1:]))


# -- adaptive batch allocation ----------------------------------------------

def test_adaptive_batch_allocation_proportional():
    alloc = lb.adaptive_batch_allocation([1, 1, 2, 4], 256)
    assert alloc.sum() == 256
    assert alloc[3] > alloc[2] > alloc[0]
    # per-worker time is near-equal
    t = alloc / np.array([1, 1, 2, 4])
    assert t.max() / t.min() < 1.2


def test_straggler_dropk():
    w = lb.straggler_dropk_weights([5, 1, 2, 3, 4], drop_k=1)
    assert w[0] == 0.0               # slowest (highest arrival) dropped
    np.testing.assert_allclose(w.sum(), 1.0)


# -- async delay compensation (Eq. 12) ---------------------------------------

def quad_problem(seed=1):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)
    A = A @ A.T / 8 + jnp.eye(8)

    def loss(p, b):
        return 0.5 * p @ A @ p + b @ p

    stream = [jnp.asarray(rng.normal(size=8) * 0.01, jnp.float32)
              for _ in range(60)]
    return loss, stream


def test_delay_compensation_beats_naive_async():
    loss, stream = quad_problem()
    p0 = jnp.ones(8)
    cfg_c = async_dp.AsyncConfig(max_staleness=6, compensate=True, lr=0.15,
                                 staleness="straggler")
    cfg_n = async_dp.AsyncConfig(max_staleness=6, compensate=False, lr=0.15,
                                 staleness="straggler")
    _, l_comp = async_dp.simulate_async_sgd(loss, p0, stream, cfg_c)
    _, l_naive = async_dp.simulate_async_sgd(loss, p0, stream, cfg_n)
    _, l_sync = async_dp.simulate_sync_sgd(loss, p0, stream, 0.15)
    # paper's qualitative ordering: sync <= compensated < naive
    assert l_comp[-1] < l_naive[-1]
    assert l_sync[-1] <= l_comp[-1] + 1e-3


def test_async_converges_with_zero_staleness():
    loss, stream = quad_problem(2)
    p0 = jnp.ones(8)
    cfg = async_dp.AsyncConfig(max_staleness=0, compensate=True, lr=0.15)
    _, l_async = async_dp.simulate_async_sgd(loss, p0, stream, cfg)
    _, l_sync = async_dp.simulate_sync_sgd(loss, p0, stream, 0.15)
    np.testing.assert_allclose(l_async[-1], l_sync[-1], atol=1e-5)


# -- hybrid planner -----------------------------------------------------------

def test_model_flops_close_to_6nd():
    cfg = get_arch("internlm2-20b")
    f = hybrid.model_flops(cfg, 4096, 256)
    six_nd = 6 * cfg.num_params() * 4096 * 256
    assert 0.9 < f / six_nd < 1.3    # attention quadratic adds ~10%


def test_moe_flops_use_active_params():
    cfg = get_arch("qwen3-moe-30b-a3b")
    f = hybrid.model_flops(cfg, 4096, 256)
    six_nd_active = 6 * cfg.active_params() * 4096 * 256
    six_nd_full = 6 * cfg.num_params() * 4096 * 256
    assert f < 0.5 * six_nd_full
    assert 0.8 < f / six_nd_active < 1.8


def test_auto_plan_remats_training():
    import jax
    mesh = make_host_mesh()
    plan = hybrid.auto_plan(get_arch("internlm2-20b"), mesh,
                            SHAPES["train_4k"], ParallelConfig())
    assert plan.remat
    plan_d = hybrid.auto_plan(get_arch("internlm2-20b"), mesh,
                              SHAPES["decode_32k"], ParallelConfig())
    assert not plan_d.remat


# -- straggler simulation ------------------------------------------------------

def test_straggler_policies_ordering():
    sim = straggler.StragglerSim(n_workers=8, hetero_cv=0.4, flaky_prob=0.1)
    out = straggler.compare_policies(sim, global_batch=1024, steps=300)
    # adaptive allocation beats uniform under heterogeneity
    assert out["adaptive"]["throughput"] > out["uniform"]["throughput"]
    # dropk trades useful samples for speed but throughput >= uniform
    assert out["dropk"]["throughput"] > out["uniform"]["throughput"]
    assert out["dropk"]["useful_frac"] < 1.0
