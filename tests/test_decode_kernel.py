"""Flash-decode Pallas kernel (interpret mode on CPU): length-skipping
parity with the pure-jnp oracle AND the dense model-stack decode paths
across ragged length vectors — every masking variant (full cache, sliding
window, gemma ring wraparound) plus the int8 in-kernel-dequant fusion."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels import ops, ref
from repro.models import attention as attn_lib
from repro.models import kvquant as kq

B, S, H, Hk, D = 4, 64, 4, 2, 16


def _qkv_cache(seed=0, s=S, h=H, hk=Hk, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, h, D), dtype)
    k = jax.random.normal(ks[1], (B, s, hk, D), dtype)
    v = jax.random.normal(ks[2], (B, s, hk, D), dtype)
    return q, k, v


RAGGED = [
    [0, 0, 0, 0],                        # every slot empty
    [S, S, S, S],                        # every slot full
    [0, 1, S // 2 + 3, S],               # empty / single / mid / full
    [5, 17, 40, 63],
]


@pytest.mark.parametrize("lengths", RAGGED)
@pytest.mark.parametrize("block_k", [8, 16, 64])
def test_flash_decode_matches_oracle_and_dense(lengths, block_k):
    q, k, v = _qkv_cache()
    lens = jnp.asarray(lengths, jnp.int32)
    out = ops.flash_decode(q, k, v, lens, block_k=block_k)
    want = ref.decode_attention(q, k, v, lens)
    assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    dense = attn_lib.decode_attention(q, k, v, lens, impl="dense")
    assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5,
                    rtol=2e-5)


def test_flash_decode_gqa_and_mqa_head_groups():
    for h, hk in ((4, 1), (8, 2), (2, 2)):
        q, k, v = _qkv_cache(seed=1, h=h, hk=hk)
        lens = jnp.asarray([3, 0, 29, S], jnp.int32)
        out = ops.flash_decode(q, k, v, lens, block_k=16)
        want = ref.decode_attention(q, k, v, lens)
        assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                        rtol=2e-5, err_msg=f"H={h} Hk={hk}")


@pytest.mark.parametrize("window", [5, 12, 100])   # incl. window > len
def test_flash_decode_sliding_window_band(window):
    q, k, v = _qkv_cache(seed=2)
    lens = jnp.asarray([0, 3, 33, S], jnp.int32)
    out = ops.flash_decode(q, k, v, lens, window=window, block_k=8)
    want = ref.decode_attention(q, k, v, lens, window=window)
    assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    dense = attn_lib.decode_attention(q, k, v, lens, window=window,
                                      impl="dense")
    assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5,
                    rtol=2e-5)


@pytest.mark.parametrize("window", [16, 7])
def test_flash_decode_ring_wraparound(window):
    """Ring cache of 16 rows, lengths beyond the ring (wrapped) and below
    it; wrap band masking must match the oracle and the dense ring path."""
    ring = 16
    q, k, v = _qkv_cache(seed=3, s=ring)
    lens = jnp.asarray([0, 3, ring, 37], jnp.int32)
    out = ops.flash_decode(q, k, v, lens, window=window, ring=True,
                           block_k=8)
    want = ref.decode_attention(q, k, v, lens, window=window, ring=True)
    assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    dense = attn_lib.decode_attention(q, k, v, lens, window=window,
                                      ring=True, impl="dense")
    assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5,
                    rtol=2e-5)


@pytest.mark.parametrize("lengths", RAGGED)
def test_flash_decode_quant_matches_oracle_and_dense(lengths):
    q, k, v = _qkv_cache(seed=4)
    k_q, k_s = kq.quantize_kv(k)
    v_q, v_s = kq.quantize_kv(v)
    lens = jnp.asarray(lengths, jnp.int32)
    out = ops.flash_decode_quant(q, k_q, k_s, v_q, v_s, lens, block_k=16)
    want = ref.decode_attention_quant(q, k_q, k_s, v_q, v_s, lens)
    assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    dense = kq.decode_attention_quant(q, k_q, k_s, v_q, v_s, lens,
                                      impl="dense")
    assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5,
                    rtol=2e-5)


def test_flash_decode_property_sweep():
    """Property-style sweep: many random ragged length vectors (always
    including 0 and S_max) stay within tight f32 tolerance of the dense
    path for both the bf16-layout and int8 kernels."""
    q, k, v = _qkv_cache(seed=5)
    k_q, k_s = kq.quantize_kv(k)
    v_q, v_s = kq.quantize_kv(v)
    rng = np.random.default_rng(0)
    for trial in range(12):
        lens = rng.integers(0, S + 1, size=B)
        lens[trial % B] = 0 if trial % 2 else S          # pin the extremes
        lens = jnp.asarray(lens, jnp.int32)
        bk = int(rng.choice([8, 16, 32]))
        out = ops.flash_decode(q, k, v, lens, block_k=bk)
        dense = attn_lib.decode_attention(q, k, v, lens, impl="dense")
        assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5,
                        rtol=2e-5, err_msg=f"trial {trial} lens {lens}")
        outq = ops.flash_decode_quant(q, k_q, k_s, v_q, v_s, lens,
                                      block_k=bk)
        denseq = kq.decode_attention_quant(q, k_q, k_s, v_q, v_s, lens)
        assert_allclose(np.asarray(outq), np.asarray(denseq), atol=2e-5,
                        rtol=2e-5, err_msg=f"trial {trial} lens {lens}")


def test_decode_attention_impl_dispatch():
    q, k, v = _qkv_cache(seed=6)
    lens = jnp.asarray([5, 0, 40, S], jnp.int32)
    flash = attn_lib.decode_attention(q, k, v, lens, impl="flash",
                                      block_k=16)
    dense = attn_lib.decode_attention(q, k, v, lens, impl="dense")
    assert_allclose(np.asarray(flash), np.asarray(dense), atol=2e-5,
                    rtol=2e-5)
    with pytest.raises(ValueError):
        attn_lib.decode_attention(q, k, v, lens, impl="nope")


def test_empty_slot_outputs_are_exact_zero():
    """len == 0 slots are defined to output zeros on every path (the dense
    softmax would otherwise emit the mean of garbage cache rows)."""
    q, k, v = _qkv_cache(seed=7)
    lens = jnp.asarray([0, 0, 7, 0], jnp.int32)
    for out in (ops.flash_decode(q, k, v, lens, block_k=8),
                attn_lib.decode_attention(q, k, v, lens, impl="dense"),
                ref.decode_attention(q, k, v, lens)):
        o = np.asarray(out)
        assert np.all(o[[0, 1, 3]] == 0.0)
        assert np.any(o[2] != 0.0)


def test_modeled_flash_bytes_below_dense_at_low_utilization():
    """The roofline term the CI serve gate checks: at mean utilization
    < 50% of S_max the flash-decode kernel's modeled bytes/step are
    strictly below the dense path's, and int8 halves them again."""
    from repro.config import get_arch
    from repro.serving.roofline import decode_attn_read_bytes
    cfg = get_arch("olmo-1b")
    rng = np.random.default_rng(1)
    lengths = rng.integers(0, 2048, size=32).tolist()   # ~25% of 4096
    dense = decode_attn_read_bytes(cfg, lengths, 4096, impl="dense")
    flash = decode_attn_read_bytes(cfg, lengths, 4096, impl="flash")
    fused = decode_attn_read_bytes(cfg, lengths, 4096, impl="flash",
                                   kv_bits=8)
    assert flash["mean_utilization"] < 0.5
    assert flash["attn_read_bytes_per_step"] \
        < dense["attn_read_bytes_per_step"]
    assert fused["attn_read_bytes_per_step"] \
        < flash["attn_read_bytes_per_step"]
    # full slots erase the advantage — dense == flash at 100% utilization
    full = [4096] * 32
    d_full = decode_attn_read_bytes(cfg, full, 4096, impl="dense")
    f_full = decode_attn_read_bytes(cfg, full, 4096, impl="flash")
    assert f_full["attn_read_bytes_per_step"] == \
        d_full["attn_read_bytes_per_step"]


def test_quant_decode_step_flash_matches_dense():
    """The fused uniform int8 decode body (kvquant.quant_decode_step) is
    logit-stable under the flash impl."""
    from repro.config import get_arch, reduced
    from repro.models import transformer as tf
    cfg = dataclasses.replace(reduced(get_arch("olmo-1b")), dtype="float32")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    cache = kq.init_model_quant_cache(cfg, 2, 32)
    cache["len"] = jnp.asarray([4, 9], jnp.int32)
    toks = jnp.asarray([[5], [11]], jnp.int32)
    ld, _ = kq.quant_decode_step(cfg, params, cache, toks,
                                 tf.ModelCtx(attn_chunk=8))
    lf, _ = kq.quant_decode_step(
        cfg, params, cache, toks,
        tf.ModelCtx(attn_chunk=8, decode_impl="flash", decode_block_k=8))
    assert_allclose(np.asarray(lf), np.asarray(ld), atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# paged variants: pool + block-table addressing must be a pure relabeling of
# the dense cache — same outputs under scrambled physical block placement
# ---------------------------------------------------------------------------

def _paged_from_dense(k, v, bs, num_extra=3, seed=11):
    """Scatter a dense (B, S, Hk, D) cache into a shuffled block pool.

    Physical block ids are a random permutation (never 0: the null sink),
    interleaved across slots, with spare blocks left as garbage — the
    adversarial layout a busy pool produces."""
    b, s = k.shape[0], k.shape[1]
    nb = s // bs
    total = b * nb + 1 + num_extra
    rng = np.random.default_rng(seed)
    perm = rng.permutation(np.arange(1, total))[:b * nb]
    tables = jnp.asarray(perm.reshape(b, nb), jnp.int32)
    kp = (jax.random.normal(jax.random.PRNGKey(99),
                            (total, bs) + k.shape[2:]) * 10
          ).astype(k.dtype)                             # garbage everywhere
    vp = (jax.random.normal(jax.random.PRNGKey(98),
                            (total, bs) + v.shape[2:]) * 10
          ).astype(v.dtype)
    kp = kp.at[perm].set(k.reshape(b * nb, bs, *k.shape[2:]))
    vp = vp.at[perm].set(v.reshape(b * nb, bs, *v.shape[2:]))
    return kp, vp, tables


@pytest.mark.parametrize("lengths", RAGGED)
@pytest.mark.parametrize("bs", [8, 16])
def test_paged_flash_decode_matches_dense(lengths, bs):
    q, k, v = _qkv_cache(seed=8)
    kp, vp, tables = _paged_from_dense(k, v, bs)
    lens = jnp.asarray(lengths, jnp.int32)
    from repro.kernels import decode_attention as dk
    out = dk.flash_decode_attention_paged(q, kp, vp, tables, lens,
                                          interpret=True)
    want = ref.decode_attention_paged(q, kp, vp, tables, lens)
    assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    dense = ops.flash_decode(q, k, v, lens, block_k=bs)
    assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5,
                    rtol=2e-5)


@pytest.mark.parametrize("window,ring", [(12, False), (7, True)])
def test_paged_flash_decode_window_and_ring(window, ring):
    s = 16 if ring else S
    q, k, v = _qkv_cache(seed=9, s=s)
    kp, vp, tables = _paged_from_dense(k, v, bs=8)
    lens = jnp.asarray([0, 3, s, 37 if ring else s - 1], jnp.int32)
    from repro.kernels import decode_attention as dk
    out = dk.flash_decode_attention_paged(q, kp, vp, tables, lens,
                                          window=window, ring=ring,
                                          interpret=True)
    want = ref.decode_attention(q, k, v, lens, window=window, ring=ring)
    assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)


N_LAYERS = 3


@pytest.mark.parametrize("impl", ["ref", "dense", "flash"])
@pytest.mark.parametrize("layer", range(N_LAYERS))
def test_paged_decode_reads_one_layer_of_a_stacked_pool(layer, impl):
    """A layer-stacked pool (L, N, bs, Hk, D) with a traced layer index
    attends exactly that layer: it matches the oracle on the layer's own
    4-D pool, and the flash kernel given that 4-D pool (the L = 1 case)
    gives the same bits."""
    from repro.cache_layout import CacheLayout
    from repro.kernels import decode_attention as dk
    bs = 8
    q = _qkv_cache(seed=30)[0]
    pools = [_paged_from_dense(*_qkv_cache(seed=31 + i)[1:], bs)
             for i in range(N_LAYERS)]
    tables = pools[0][2]
    kp = jnp.stack([p[0] for p in pools])
    vp = jnp.stack([p[1] for p in pools])
    lens = jnp.asarray([5, 0, 40, S], jnp.int32)
    lay = CacheLayout(kind="paged", impl=impl, block_size=bs)
    out = jax.jit(lambda kp, vp, l: ops.decode_attention(
        q, {"k": kp, "v": vp, "block_table": tables}, lens, layout=lay,
        layer=l))(kp, vp, jnp.int32(layer))
    want = ref.decode_attention_paged(q, kp[layer], vp[layer], tables, lens)
    assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5, rtol=2e-5)
    if impl == "flash":
        one = dk.flash_decode_attention_paged(q, kp[layer], vp[layer],
                                              tables, lens, interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(one))


@pytest.mark.parametrize("lengths", RAGGED)
def test_paged_flash_decode_quant_matches_dense(lengths):
    q, k, v = _qkv_cache(seed=10)
    k_q, k_s = kq.quantize_kv(k)
    v_q, v_s = kq.quantize_kv(v)
    bs = 16
    kqp, vqp, tables = _paged_from_dense(k_q, v_q, bs)
    ksp, vsp, _ = _paged_from_dense(k_s.astype(jnp.float32)[..., None],
                                    v_s[..., None], bs)
    ksp, vsp = ksp[..., 0], vsp[..., 0]
    lens = jnp.asarray(lengths, jnp.int32)
    from repro.kernels import decode_attention as dk
    out = dk.flash_decode_attention_paged_quant(
        q, kqp, ksp, vqp, vsp, tables, lens, interpret=True)
    dense = ops.flash_decode_quant(q, k_q, k_s, v_q, v_s, lens, block_k=bs)
    assert_allclose(np.asarray(out), np.asarray(dense), atol=2e-5,
                    rtol=2e-5)


def test_unified_decode_attention_dispatch():
    """ops.decode_attention: one entry point, every cell of the
    (dense|paged) x (bf16|int8) x (ref|dense|flash) matrix agrees."""
    from repro.cache_layout import CacheLayout
    q, k, v = _qkv_cache(seed=12)
    k_q, k_s = kq.quantize_kv(k)
    v_q, v_s = kq.quantize_kv(v)
    bs = 16
    kp, vp, tables = _paged_from_dense(k, v, bs)
    kqp, vqp, _ = _paged_from_dense(k_q, v_q, bs)
    ksp, vsp, _ = _paged_from_dense(k_s[..., None], v_s[..., None], bs)
    ksp, vsp = ksp[..., 0], vsp[..., 0]
    lens = jnp.asarray([5, 0, 40, S], jnp.int32)
    golden = ref.decode_attention(q, k, v, lens)
    golden_q = ref.decode_attention_quant(q, k_q, k_s, v_q, v_s, lens)
    for impl in ("ref", "dense", "flash"):
        lay = CacheLayout(impl=impl, block_size=bs)
        out = ops.decode_attention(q, {"k": k, "v": v}, lens, layout=lay)
        assert_allclose(np.asarray(out), np.asarray(golden), atol=2e-5,
                        rtol=2e-5, err_msg=f"dense16 {impl}")
        out = ops.decode_attention(
            q, {"k": kp, "v": vp, "block_table": tables}, lens,
            layout=lay.replace(kind="paged"))
        assert_allclose(np.asarray(out), np.asarray(golden), atol=2e-5,
                        rtol=2e-5, err_msg=f"paged16 {impl}")
        out = ops.decode_attention(
            q, {"k_q": k_q, "k_s": k_s, "v_q": v_q, "v_s": v_s}, lens,
            layout=lay.replace(kv_bits=8))
        assert_allclose(np.asarray(out), np.asarray(golden_q), atol=2e-5,
                        rtol=2e-5, err_msg=f"dense8 {impl}")
        out = ops.decode_attention(
            q, {"k_q": kqp, "k_s": ksp, "v_q": vqp, "v_s": vsp,
                "block_table": tables}, lens,
            layout=lay.replace(kind="paged", kv_bits=8))
        assert_allclose(np.asarray(out), np.asarray(golden_q), atol=2e-5,
                        rtol=2e-5, err_msg=f"paged8 {impl}")
    with pytest.raises(ValueError):
        ops.decode_attention(q, {"k_q": k_q, "k_s": k_s, "v_q": v_q,
                                 "v_s": v_s}, lens,
                             layout=CacheLayout(kv_bits=8, window=8))


# ---------------------------------------------------------------------------
# speculative k-row verification: q (B, Sq, H, D) + per-slot q_lens
# ---------------------------------------------------------------------------

K_SPEC = 4


def _spec_q(seed=20, k=K_SPEC, h=H):
    return jax.random.normal(jax.random.PRNGKey(seed), (B, k, H, D),
                             jnp.float32)


def _rowwise(call, q, lens, q_lens):
    """Ground truth for the k-row contract: row ``j`` of the fused call
    must equal a single-row decode at ``lengths + j`` (the length sequence
    row-by-row decode would present); rows ``>= q_lens`` are exact zero."""
    k = q.shape[1]
    want = np.zeros((B, k, q.shape[2], q.shape[3]), np.float32)
    ql = np.asarray(q_lens)
    for j in range(k):
        single = np.asarray(call(q[:, j:j + 1], lens + j))
        for b in range(B):
            if j < ql[b]:
                want[b, j] = single[b, 0]
    return want


@pytest.mark.parametrize("q_lens", [[1, 1, 1, 1], [4, 4, 4, 4],
                                    [1, 2, 3, 4]])
def test_spec_rows_match_single_row_full_cache(q_lens):
    """Fused k-row verification == k independent single-row decodes at
    stepped lengths (accept-0 => only row 0 live; accept-all => every
    row), dead rows exact zero — flash and dense paths, incl. len near
    S_max (the deepest live row touches the last cache row)."""
    q, k, v = _qkv_cache(seed=21)
    qk = _spec_q(seed=22)
    lens = jnp.asarray([1, 5, 40, S - K_SPEC], jnp.int32)
    ql = jnp.asarray(q_lens, jnp.int32)
    want = _rowwise(lambda qj, lj: ops.flash_decode(qj, k, v, lj,
                                                    block_k=16),
                    qk, lens, ql)
    out = ops.flash_decode(qk, k, v, lens, block_k=16, q_lens=ql)
    assert_allclose(np.asarray(out), want, atol=2e-5, rtol=2e-5)
    dense = attn_lib.decode_attention(qk, k, v, lens, impl="dense",
                                      q_lens=ql)
    assert_allclose(np.asarray(dense), want, atol=2e-5, rtol=2e-5)
    oracle = ref.decode_attention(qk, k, v, lens, q_lens=ql)
    assert_allclose(np.asarray(oracle), want, atol=2e-5, rtol=2e-5)
    qlm = np.asarray(ql)
    for b in range(B):
        assert np.all(np.asarray(out)[b, qlm[b]:] == 0.0), \
            f"slot {b}: dead draft rows must be exact zero"


@pytest.mark.parametrize("window", [2, 7])      # window < k and window > k
def test_spec_rows_sliding_window_smaller_than_draft(window):
    q, k, v = _qkv_cache(seed=23)
    qk = _spec_q(seed=24)
    lens = jnp.asarray([1, 5, 40, S - K_SPEC], jnp.int32)
    ql = jnp.asarray([1, 4, 2, 4], jnp.int32)
    want = _rowwise(
        lambda qj, lj: ops.flash_decode(qj, k, v, lj, window=window,
                                        block_k=8), qk, lens, ql)
    out = ops.flash_decode(qk, k, v, lens, window=window, block_k=8,
                           q_lens=ql)
    assert_allclose(np.asarray(out), want, atol=2e-5, rtol=2e-5)
    dense = attn_lib.decode_attention(qk, k, v, lens, window=window,
                                      impl="dense", q_lens=ql)
    assert_allclose(np.asarray(dense), want, atol=2e-5, rtol=2e-5)


def test_spec_rows_ring_wraparound_mid_draft():
    """Gemma ring sized window + k - 1 (the spec margin): draft rows whose
    ring positions wrap mid-draft still reduce to the stepped single-row
    decode."""
    ring, window = 16, 13                       # margin = K_SPEC - 1
    q, k, v = _qkv_cache(seed=25, s=ring)
    qk = _spec_q(seed=26)
    # lengths past the ring: every draft row wraps
    lens = jnp.asarray([2, 12, 30, 37], jnp.int32)
    ql = jnp.asarray([4, 3, 1, 4], jnp.int32)
    want = _rowwise(
        lambda qj, lj: ops.flash_decode(qj, k, v, lj, window=window,
                                        ring=True, block_k=8),
        qk, lens, ql)
    out = ops.flash_decode(qk, k, v, lens, window=window, ring=True,
                           block_k=8, q_lens=ql)
    assert_allclose(np.asarray(out), want, atol=2e-5, rtol=2e-5)
    dense = attn_lib.decode_attention(qk, k, v, lens, window=window,
                                      ring=True, impl="dense", q_lens=ql)
    assert_allclose(np.asarray(dense), want, atol=2e-5, rtol=2e-5)


def test_spec_rows_int8_dense_and_paged():
    q, k, v = _qkv_cache(seed=27)
    qk = _spec_q(seed=28)
    k_q, k_s = kq.quantize_kv(k)
    v_q, v_s = kq.quantize_kv(v)
    lens = jnp.asarray([1, 7, 33, S - K_SPEC], jnp.int32)
    ql = jnp.asarray([2, 4, 1, 3], jnp.int32)
    want = _rowwise(
        lambda qj, lj: ops.flash_decode_quant(qj, k_q, k_s, v_q, v_s, lj,
                                              block_k=16), qk, lens, ql)
    out = ops.flash_decode_quant(qk, k_q, k_s, v_q, v_s, lens, block_k=16,
                                 q_lens=ql)
    assert_allclose(np.asarray(out), want, atol=2e-5, rtol=2e-5)
    dense = kq.decode_attention_quant(qk, k_q, k_s, v_q, v_s, lens,
                                      q_lens=ql)
    assert_allclose(np.asarray(dense), want, atol=2e-5, rtol=2e-5)
    # paged int8 through the unified layout dispatch
    from repro.cache_layout import CacheLayout
    bs = 16
    kqp, vqp, tables = _paged_from_dense(k_q, v_q, bs)
    ksp, vsp, _ = _paged_from_dense(k_s[..., None], v_s[..., None], bs)
    ksp, vsp = ksp[..., 0], vsp[..., 0]
    for impl in ("dense", "flash"):
        outp = ops.decode_attention(
            qk, {"k_q": kqp, "k_s": ksp, "v_q": vqp, "v_s": vsp,
                 "block_table": tables}, lens,
            layout=CacheLayout(kind="paged", kv_bits=8, impl=impl,
                               block_size=bs), q_lens=ql)
        assert_allclose(np.asarray(outp), want, atol=2e-5, rtol=2e-5,
                        err_msg=f"paged8 {impl}")


def test_spec_rows_paged_block_boundary():
    """Draft spans crossing physical block boundaries (len % bs near bs)
    read the right blocks for every row."""
    from repro.cache_layout import CacheLayout
    q, k, v = _qkv_cache(seed=29)
    qk = _spec_q(seed=30)
    bs = 8
    kp, vp, tables = _paged_from_dense(k, v, bs)
    # rows straddle a boundary: len+j crosses a multiple of bs mid-draft
    lens = jnp.asarray([7, 8, 15, 39], jnp.int32)
    ql = jnp.asarray([4, 4, 3, 4], jnp.int32)
    want = _rowwise(
        lambda qj, lj: ops.decode_attention(
            qj, {"k": kp, "v": vp, "block_table": tables}, lj,
            layout=CacheLayout(kind="paged", impl="dense", block_size=bs)),
        qk, lens, ql)
    for impl in ("dense", "flash"):
        out = ops.decode_attention(
            qk, {"k": kp, "v": vp, "block_table": tables}, lens,
            layout=CacheLayout(kind="paged", impl=impl, block_size=bs),
            q_lens=ql)
        assert_allclose(np.asarray(out), want, atol=2e-5, rtol=2e-5,
                        err_msg=impl)


def test_spec_rows_property_sweep():
    """Random ragged (lengths, q_lens) pairs — always pinning the accept-0
    (q_len 1) and accept-all (q_len k) extremes — keep flash == dense ==
    stepped single-row across the full and int8 variants."""
    q, k, v = _qkv_cache(seed=31)
    k_q, k_s = kq.quantize_kv(k)
    v_q, v_s = kq.quantize_kv(v)
    rng = np.random.default_rng(7)
    for trial in range(8):
        qk = _spec_q(seed=40 + trial)
        lens = rng.integers(1, S - K_SPEC + 1, size=B)
        ql = rng.integers(1, K_SPEC + 1, size=B)
        ql[trial % B] = 1 if trial % 2 else K_SPEC      # pin the extremes
        lens_j = jnp.asarray(lens, jnp.int32)
        ql_j = jnp.asarray(ql, jnp.int32)
        want = _rowwise(lambda qj, lj: ops.flash_decode(qj, k, v, lj,
                                                        block_k=16),
                        qk, lens_j, ql_j)
        out = ops.flash_decode(qk, k, v, lens_j, block_k=16, q_lens=ql_j)
        assert_allclose(np.asarray(out), want, atol=2e-5, rtol=2e-5,
                        err_msg=f"trial {trial} lens {lens} ql {ql}")
        dense = attn_lib.decode_attention(qk, k, v, lens_j, impl="dense",
                                          q_lens=ql_j)
        assert_allclose(np.asarray(dense), want, atol=2e-5, rtol=2e-5,
                        err_msg=f"trial {trial} dense")
        wq = _rowwise(
            lambda qj, lj: ops.flash_decode_quant(qj, k_q, k_s, v_q, v_s,
                                                  lj, block_k=16),
            qk, lens_j, ql_j)
        outq = ops.flash_decode_quant(qk, k_q, k_s, v_q, v_s, lens_j,
                                      block_k=16, q_lens=ql_j)
        assert_allclose(np.asarray(outq), wq, atol=2e-5, rtol=2e-5,
                        err_msg=f"trial {trial} int8")


def test_verify_greedy_accept_semantics():
    """verify_greedy: accepts == 1 + length of the matched draft prefix,
    clamped to q_lens — the accept-0-of-k case still commits the row-0
    emission (one token, exactly single-step decode)."""
    from repro.models import transformer as tf
    V = 11
    g = np.array([[3, 5, 7, 2], [3, 5, 7, 2], [3, 5, 7, 2]])
    logits = np.full((3, 4, V), -10.0, np.float32)
    for b in range(3):
        for j in range(4):
            logits[b, j, g[b, j]] = 10.0
    toks = np.array([
        [1, 9, 9, 9],       # no draft matches -> accept 1
        [1, 3, 5, 7],       # full match -> accept 4
        [1, 3, 5, 9],       # 2-prefix matches -> accept 3
    ], np.int32)
    acc = tf.verify_greedy(jnp.asarray(toks), jnp.asarray(logits),
                           jnp.asarray([4, 4, 4], jnp.int32))
    assert list(np.asarray(acc)) == [1, 4, 3]
    # q_lens caps the accept even when later rows would match
    acc = tf.verify_greedy(jnp.asarray(toks), jnp.asarray(logits),
                           jnp.asarray([4, 2, 1], jnp.int32))
    assert list(np.asarray(acc)) == [1, 2, 1]
