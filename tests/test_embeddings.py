"""Sharded sparse-embedding subsystem: placement math, dedup lookup,
Pallas kernel vs ref parity, sparse gradients, and bit-for-bit parity of
every sharding plan on a 1-device mesh (the multi-device parity lives in
``distributed_checks.py``)."""
import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np
import pytest

from repro import embeddings
from repro.embeddings import update as embed_update
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh


def _table(rows=64, dim=16, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(rows, dim)), jnp.float32)


def _zipf_ids(n, rows, seed=1):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.minimum(rng.zipf(1.3, n) - 1, rows - 1), jnp.int32)


# ---------------------------------------------------------------------------
# placement math
# ---------------------------------------------------------------------------

def test_plan_shard_shapes_and_bytes():
    spec = embeddings.EmbedSpec("t", rows=128, dim=64)
    mesh = {"data": 2, "model": 4}
    assert embeddings.shard_shape(
        spec, embeddings.make_plan("replicated"), mesh) == (128, 64)
    assert embeddings.shard_shape(
        spec, embeddings.make_plan("row"), mesh) == (32, 64)
    assert embeddings.shard_shape(
        spec, embeddings.make_plan("col"), mesh) == (128, 32)
    assert embeddings.shard_shape(
        spec, embeddings.make_plan("row_col"), mesh) == (32, 32)
    # 2D sharding: per-device memory shrinks ~1/N with total shards
    full = embeddings.shard_bytes(
        spec, embeddings.make_plan("replicated"), mesh)
    two_d = embeddings.shard_bytes(
        spec, embeddings.make_plan("row_col"), mesh)
    assert two_d == full // 8


def test_plan_validation():
    with pytest.raises(ValueError):
        embeddings.EmbedPlan(kind="row")            # missing row_axis
    with pytest.raises(ValueError):
        embeddings.EmbedPlan(kind="replicated", row_axis="model")
    with pytest.raises(ValueError):
        embeddings.EmbedPlan(kind="bogus")
    spec = embeddings.EmbedSpec("t", rows=100, dim=64)
    with pytest.raises(ValueError):                 # 100 % 8 != 0
        embeddings.shard_shape(spec, embeddings.make_plan(
            "row", row_axis="model"), {"model": 8})


def test_exchange_model_sharded_beats_replicated():
    """The cost model agrees with the benchmark's claim: row/col/2D move
    fewer bytes than the replicated-dense grad all-reduce, and sparse
    sync beats dense replicated."""
    spec = embeddings.EmbedSpec("t", rows=16384, dim=64)
    mesh = {"data": 8, "model": 4}
    rep = embeddings.exchange_bytes(
        spec, embeddings.make_plan("replicated"), mesh, 128)["total"]
    for kind in ("row", "col", "row_col"):
        ex = embeddings.exchange_bytes(
            spec, embeddings.make_plan(kind), mesh, 128)["total"]
        assert ex < rep, kind
    assert embeddings.sparse_exchange_bytes(spec, mesh, 128) < rep


# ---------------------------------------------------------------------------
# dedup lookup + kernels
# ---------------------------------------------------------------------------

def test_dedup_lookup_bitwise_equals_gather():
    table = _table()
    ids = _zipf_ids(40, 64)
    want = np.asarray(table)[np.asarray(ids)]
    np.testing.assert_array_equal(
        np.asarray(embeddings.dedup_lookup(table, ids)), want)
    np.testing.assert_array_equal(
        np.asarray(embeddings.dedup_lookup(table, ids, use_kernel=True)),
        want)
    # 2D id shapes keep their leading dims
    ids2 = ids.reshape(8, 5)
    out = embeddings.dedup_lookup(table, ids2)
    assert out.shape == (8, 5, 16)
    np.testing.assert_array_equal(np.asarray(out), want.reshape(8, 5, 16))


def test_gather_kernel_matches_ref():
    table = _table(rows=128, dim=32)
    ids = _zipf_ids(48, 128)
    np.testing.assert_array_equal(
        np.asarray(ops.embedding_gather(table, ids)),
        np.asarray(ops.embedding_gather(table, ids, impl="ref")))


def test_scatter_add_kernel_matches_ref_with_duplicates():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(24, 16)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 8, 24), jnp.int32)   # heavy dupes
    got = ops.embedding_scatter_add(x, idx, 8)
    want = ops.embedding_scatter_add(x, idx, 8, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# sparse gradients
# ---------------------------------------------------------------------------

def test_sparse_grad_from_lookup_equals_autodiff():
    table = _table()
    ids = _zipf_ids(32, 64)
    tgt = jnp.asarray(np.random.default_rng(4).normal(size=(32, 16)),
                      jnp.float32)

    def loss(t):
        return 0.5 * jnp.sum((t[ids] - tgt) ** 2)

    dense = jax.grad(loss)(table)
    dout = table[ids] - tgt                       # d loss / d lookup
    for use_kernel in (False, True):
        u, rows = embed_update.sparse_grad_from_lookup(
            dout, ids, 64, use_kernel=use_kernel)
        rebuilt = embed_update.scatter_rows(u, rows, 64)
        np.testing.assert_allclose(np.asarray(rebuilt), np.asarray(dense),
                                   rtol=1e-6, atol=1e-6)


def test_sparse_row_sync_single_device_bitwise():
    """On a 1-device mesh the rows-touched sync IS the dense gradient."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    mesh = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    g = np.zeros((64, 16), np.float32)
    ids = np.asarray(_zipf_ids(20, 64))
    rng = np.random.default_rng(5)
    for j in ids:
        g[j] += rng.normal(size=16).astype(np.float32)

    f = shard_map(
        lambda gs, i: embed_update.sparse_row_sync(gs, i, ("data",)),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False)
    out = f(jnp.asarray(g), jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(out), g)


def test_row_compressor_keeps_topk_per_row():
    rows = jnp.asarray(np.random.default_rng(6).normal(size=(8, 16)),
                       jnp.float32)
    comp = embed_update.make_row_compressor("topk", k=4)
    kept = np.asarray(comp(rows))
    for r in range(8):
        nz = np.nonzero(kept[r])[0]
        assert len(nz) == 4
        # the kept entries are the 4 largest magnitudes, values unchanged
        want = np.argsort(-np.abs(np.asarray(rows[r])))[:4]
        assert set(nz) == set(want)
        np.testing.assert_array_equal(kept[r, nz], np.asarray(rows)[r, nz])


# ---------------------------------------------------------------------------
# sharding plans on a 1-device mesh: bit-for-bit vs the replicated gather
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", embeddings.PLANS)
def test_sharded_lookup_single_device_bitwise(kind):
    mesh = make_host_mesh()
    spec = embeddings.EmbedSpec("t", rows=64, dim=16)
    plan = embeddings.make_plan(kind)
    table = _table()
    ids = _zipf_ids(32, 64)
    lk = embeddings.make_sharded_lookup(mesh, spec, plan)
    out = lk(jax.device_put(table, embeddings.named_sharding(mesh, plan)),
             ids)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(table)[np.asarray(ids)])


def test_col_plan_requires_dp_axis():
    mesh = make_host_mesh()
    spec = embeddings.EmbedSpec("t", rows=64, dim=16)
    plan = embeddings.make_plan("col", col_axis="model")
    with pytest.raises(ValueError):
        embeddings.make_sharded_lookup(mesh, spec, plan)
