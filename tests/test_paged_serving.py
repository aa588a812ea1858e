"""Paged KV serving: token-exact parity with the dense layout for every
architecture family, prefix sharing, copy-on-write, pool exhaustion, and
the CacheLayout dispatch in make_backend/serve.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.cache_layout import CacheLayout
from repro.config import get_arch, reduced
from repro.models import transformer as tf
from repro.serving import engine as eng
from repro.serving import traffic
from repro.serving.block_pool import NULL_BLOCK

FAMILY_ARCHS = {"uniform": "olmo-1b", "gemma": "gemma3-1b",
                "jamba": "jamba-v0.1-52b", "rwkv6": "rwkv6-1.6b",
                "whisper": "whisper-medium"}

PAGED = CacheLayout(kind="paged", block_size=8)


def _family_setup(fam, seed=0, n=4):
    cfg = dataclasses.replace(reduced(get_arch(FAMILY_ARCHS[fam])),
                              dtype="float32")
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(4, 12))
        frames = None
        if cfg.encoder_layers:
            f = rng.normal(0, 0.02, (cfg.encoder_frames, cfg.d_model))
            frames = tuple(tuple(float(x) for x in row) for row in f)
        reqs.append(traffic.Request(
            rid=i, user_id=i,
            prompt=tuple(int(t) for t in
                         rng.integers(3, cfg.vocab_size, plen)),
            max_new_tokens=int(rng.integers(3, 8)), arrival=0.0,
            frames=frames))
    return cfg, params, reqs


def _run(cfg, params, reqs, layout=None, n_slots=2, max_len=64, ctx=None):
    backend = eng.make_backend(cfg, params, ctx=ctx, layout=layout)
    ecfg = eng.EngineConfig(
        n_slots=n_slots, max_len=max_len,
        layout=layout if layout is not None else CacheLayout())
    engine = eng.ServingEngine(backend, ecfg)
    outputs, _, summary = engine.run(reqs)
    return outputs, summary, engine


# ---------------------------------------------------------------------------
# token-exact parity: paged == dense for every family (the paged layout is
# pure data movement — same rows, different physical addressing)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fam", sorted(FAMILY_ARCHS))
def test_paged_matches_dense_per_family(fam):
    cfg, params, reqs = _family_setup(fam)
    dense, sd, _ = _run(cfg, params, reqs)
    paged, sp, engine = _run(cfg, params, reqs, layout=PAGED)
    assert sp["finished"] == len(reqs) and sp["rejected"] == 0
    assert paged == dense, f"{fam}: paged tokens diverged from dense"
    assert "paged" in sp
    # every block returned to the pool after the batch drains
    assert engine.pool.used_blocks == 0
    assert (engine.pool.refcount[1:] == 0).all()


def test_paged_flash_and_int8_match_their_dense_twins():
    cfg, params, reqs = _family_setup("uniform")
    for lay in (CacheLayout(impl="flash"),
                CacheLayout(kv_bits=8),
                CacheLayout(kv_bits=8, impl="flash")):
        dense, _, _ = _run(cfg, params, reqs, layout=lay)
        paged, _, _ = _run(cfg, params, reqs,
                           layout=lay.replace(kind="paged", block_size=8))
        assert paged == dense, f"paged diverged from dense under {lay}"


def test_paged_backend_dispatch_matrix():
    cfg, params, _ = _family_setup("uniform")
    assert isinstance(eng.make_backend(cfg, params, layout=PAGED),
                      eng.PagedNativeBackend)
    assert isinstance(
        eng.make_backend(cfg, params,
                         layout=PAGED.replace(kv_bits=8)),
        eng.PagedInt8Backend)
    # chunked prefill needs the native cache-append path -> composition
    assert isinstance(
        eng.make_backend(cfg, params, prefill_chunk=8, layout=PAGED),
        eng.PagedSlots)
    cfg_g, params_g, _ = _family_setup("gemma", n=1)
    b = eng.make_backend(cfg_g, params_g, layout=PAGED.replace(kv_bits=8))
    assert isinstance(b, eng.PagedSlots)
    assert isinstance(b.inner, eng.Int8KVSlots)
    # int8 (paged or dense) on a KV-free family stays a clear error
    cfg_r, params_r, _ = _family_setup("rwkv6", n=1)
    with pytest.raises(ValueError):
        eng.make_backend(cfg_r, params_r, layout=PAGED.replace(kv_bits=8))


def test_decode_step_donates_the_slot_state():
    """The paged flash-decode step consumes its slot state (donation is on
    for every backend, the CPU included): the step hands back a fresh
    state, and a read of the donated one fails here as it would on the
    chip."""
    cfg, params, _ = _family_setup("uniform", n=1)
    b = eng.make_backend(cfg, params, layout=PAGED.replace(impl="flash"))
    state = b.init_slots(2, 64)
    _, new_state = b.decode(state, np.ones((2, 1), np.int32))
    donated = [x for x in jax.tree.leaves(state) if x.is_deleted()]
    assert donated, "decode kept its input slot state alive"
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(donated[0])
    assert not any(x.is_deleted() for x in jax.tree.leaves(new_state))


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_paged_decode_step_is_the_dense_step_in_place(impl):
    """One native paged decode step against the dense-layout step on the
    same rows: for every slot that owns its frontier, the logits and the
    pools (read through the tables) are bit-identical.  Nothing else in
    the pools moves but each layer's new rows and the null block: a slot
    whose write entry is the null block (a shared tail awaiting
    copy-on-write) writes there and leaves its readable blocks as they
    were."""
    import jax.numpy as jnp
    from repro.kernels import ref
    cfg = reduced(get_arch("olmo-1b"))
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    ctx = tf.ModelCtx(decode_impl=impl, decode_block_k=PAGED.block_size)
    bs, n_slots, max_len = PAGED.block_size, 4, 64
    L, nb = cfg.num_layers, max_len // bs
    lens = np.array([5, 17, 12, 33], np.int32)
    owners, shared = [0, 1, 3], 2                  # slot 2: tail not owned
    shape = (L, n_slots, max_len, cfg.num_kv_heads, cfg.head_dim)
    kd, vd = (jax.random.normal(jax.random.PRNGKey(s), shape
                                ).astype(cfg.dtype) for s in (1, 2))
    rng = np.random.default_rng(3)
    n_blocks = 1 + n_slots * nb + 5                # null + slots + spare
    table = rng.permutation(np.arange(1, n_blocks))[:n_slots * nb]
    table = table.reshape(n_slots, nb).astype(np.int32)
    write = table.copy()
    write[shared, lens[shared] // bs] = NULL_BLOCK

    def pool(dense):
        garbage = jax.random.normal(jax.random.PRNGKey(4), (L, n_blocks, bs)
                                    + shape[3:]).astype(cfg.dtype)
        return garbage.at[:, table.reshape(-1)].set(
            dense.reshape((L, n_slots * nb, bs) + shape[3:]))

    tokens = jnp.asarray(rng.integers(3, cfg.vocab_size, (n_slots, 1)),
                         jnp.int32)
    dense = {"k": kd, "v": vd, "len": jnp.asarray(lens)}
    paged = {"k": pool(kd), "v": pool(vd), "block_table": jnp.asarray(table),
             "write_table": jnp.asarray(write), "len": jnp.asarray(lens)}
    step = jax.jit(lambda c, t: tf.decode_step(cfg, params, c, t, ctx))
    want_logits, want = step(dense, tokens)
    logits, got = step(paged, tokens)
    np.testing.assert_array_equal(np.asarray(logits)[owners],
                                  np.asarray(want_logits)[owners])
    assert (np.asarray(got["len"]) == lens + 1).all()
    touched = np.zeros((L, n_blocks, bs), bool)
    touched[:, NULL_BLOCK] = True
    for b in owners:
        touched[:, table[b, lens[b] // bs], lens[b] % bs] = True
    for n in "kv":
        new, old = np.asarray(got[n]), np.asarray(paged[n])
        for layer in range(L):
            seen = np.asarray(ref.paged_gather(got[n][layer],
                                               jnp.asarray(table)))
            np.testing.assert_array_equal(seen[owners],
                                          np.asarray(want[n])[layer, owners])
        np.testing.assert_array_equal(new[~touched], old[~touched])
        # layer 0's rows come before any attention: the shared slot's row
        # is the dense step's, and it went to the null block
        np.testing.assert_array_equal(
            new[0, NULL_BLOCK, lens[shared] % bs],
            np.asarray(want[n])[0, shared, lens[shared]])


def test_paged_slots_pages_only_linear_kv_leaves():
    """The generic composition pools exactly the append-at-len KV leaves:
    gemma's window-bounded rings and whisper's cross-KV stay slot-resident;
    rwkv6 (no KV at all) degenerates to the identity composition."""
    cfg, params, _ = _family_setup("gemma", n=1)
    b = eng.make_backend(cfg, params, layout=PAGED)
    cache = b.init_slots(2, 64)
    n_pooled = sum(ax is not None for ax in b._specs)
    n_full = sum(1 for k in cfg.layer_kinds() if k == "attn")
    assert n_pooled == 2 * n_full           # k and v per full-attn layer
    assert cache["block_table"].shape == (2, 64 // PAGED.block_size)
    cfg_r, params_r, _ = _family_setup("rwkv6", n=1)
    br = eng.make_backend(cfg_r, params_r, layout=PAGED)
    br.init_slots(2, 64)
    assert all(ax is None for ax in br._specs)
    cfg_w, params_w, _ = _family_setup("whisper", n=1)
    bw = eng.make_backend(cfg_w, params_w, layout=PAGED)
    cache_w = bw.init_slots(2, 64)
    # cross-KV leaves keep their dense per-slot shape
    assert cache_w["cross_k"].shape[1] == 2


# ---------------------------------------------------------------------------
# prefix sharing + copy-on-write through the engine
# ---------------------------------------------------------------------------

def test_prefix_sharing_is_token_exact_and_actually_shares():
    cfg, params, _ = _family_setup("uniform")
    prompt = tuple(range(3, 3 + 13))        # 3 full 4-blocks + 1-token tail
    reqs = [traffic.Request(rid=i, user_id=i, prompt=prompt,
                            max_new_tokens=6, arrival=0.0, eos_id=-1)
            for i in range(3)]
    layout = CacheLayout(kind="paged", block_size=4)
    dense, _, _ = _run(cfg, params, reqs, n_slots=3)
    shared, ss, engine = _run(cfg, params, reqs, layout=layout, n_slots=3)
    assert shared == dense
    assert ss["paged"]["shared_hits"] > 0, "identical prompts never shared"
    # the shared whole-prompt tail forces a private copy at the first
    # generated token (copy-on-write), and never corrupts the sharer
    assert ss["paged"]["cow_events"] > 0, "shared tail never COW'd"
    assert engine.pool.used_blocks == 0     # all returned after drain
    # sharing off: same tokens, zero hits
    private, sp, _ = _run(cfg, params, reqs,
                          layout=layout.replace(prefix_sharing=False),
                          n_slots=3)
    assert private == dense and sp["paged"]["shared_hits"] == 0


def test_divergent_tails_share_only_complete_prefix_blocks():
    cfg, params, _ = _family_setup("uniform")
    base = tuple(range(3, 3 + 8))           # two full 4-blocks
    reqs = [traffic.Request(rid=0, user_id=0, prompt=base + (50, 51),
                            max_new_tokens=5, arrival=0.0, eos_id=-1),
            traffic.Request(rid=1, user_id=1, prompt=base + (60, 61, 62),
                            max_new_tokens=5, arrival=0.0, eos_id=-1)]
    layout = CacheLayout(kind="paged", block_size=4)
    dense, _, _ = _run(cfg, params, reqs, n_slots=2)
    shared, ss, _ = _run(cfg, params, reqs, layout=layout, n_slots=2)
    assert shared == dense
    # the two complete prefix blocks shared; the divergent tails must not
    assert ss["paged"]["shared_hits"] == 2


# ---------------------------------------------------------------------------
# pool pressure: oversubscribed pools queue, never corrupt
# ---------------------------------------------------------------------------

def test_pool_exhaustion_degrades_to_queueing():
    cfg, params, _ = _family_setup("uniform")
    rng = np.random.default_rng(3)
    # every span is exactly 3 blocks (12-token prompt + 8 new = 20 rows at
    # block_size 8), so a 6-block pool fits at most 2 of the 3 slots
    reqs = [traffic.Request(
        rid=i, user_id=i,
        prompt=tuple(int(t) for t in
                     rng.integers(3, cfg.vocab_size, 12)),
        max_new_tokens=8, arrival=0.0, eos_id=-1) for i in range(6)]
    layout = CacheLayout(kind="paged", block_size=8, num_blocks=6,
                         prefix_sharing=False)
    dense, _, _ = _run(cfg, params, reqs, n_slots=3)
    paged, sp, engine = _run(cfg, params, reqs, layout=layout, n_slots=3)
    assert sp["finished"] == len(reqs) and sp["rejected"] == 0
    assert paged == dense, "oversubscribed pool corrupted decode state"
    # the pool really was the constraint: fewer slots ran concurrently
    assert sp["max_concurrent_slots"] <= 2
    assert engine.pool.used_blocks == 0
    assert (engine.pool.refcount[1:] == 0).all()
    assert (engine.tables.read == NULL_BLOCK).all()


def test_impossible_request_is_rejected_not_stalled():
    cfg, params, _ = _family_setup("uniform")
    # span of 5 blocks can never fit a 4-block pool: reject, don't spin
    layout = CacheLayout(kind="paged", block_size=8, num_blocks=4,
                         prefix_sharing=False)
    reqs = [traffic.Request(rid=0, user_id=0,
                            prompt=tuple(range(3, 35)), max_new_tokens=8,
                            arrival=0.0, eos_id=-1),
            traffic.Request(rid=1, user_id=1, prompt=(5, 6, 7),
                            max_new_tokens=4, arrival=0.0, eos_id=-1)]
    _, sp, _ = _run(cfg, params, reqs, layout=layout, max_len=64)
    assert sp["rejected"] == 1
    assert sp["finished"] == 1              # the small request still ran


# ---------------------------------------------------------------------------
# summary metrics + legacy shims
# ---------------------------------------------------------------------------

def test_summary_reports_occupancy_and_kv_bytes():
    cfg, params, reqs = _family_setup("uniform")
    _, sd, _ = _run(cfg, params, reqs)
    _, sp, _ = _run(cfg, params, reqs, layout=PAGED)
    assert sd["max_concurrent_slots"] >= 1
    assert sp["max_concurrent_slots"] >= 1
    # dense prices slots*max_len always; paged prices live blocks only
    assert 0 < sp["kv_bytes_per_step"] < sd["kv_bytes_per_step"]


def test_legacy_kwargs_removed_raise_type_error():
    """The PR-6 deprecation window closed: kv=/decode_impl= are gone and
    raise a clear TypeError; the layout path is the only spelling."""
    cfg, params, reqs = _family_setup("uniform", n=2)
    with pytest.raises(TypeError):
        eng.make_backend(cfg, params, kv="int8", decode_impl="flash")
    with pytest.raises(TypeError):
        eng.make_backend(cfg, params, kv="int8")
    ecfg = eng.EngineConfig(n_slots=2, max_len=64)
    with pytest.raises(TypeError):
        eng.serve(cfg, params, reqs, ecfg, kv="int8")
    # the layout spelling serves fine
    b = eng.make_backend(cfg, params,
                         layout=CacheLayout(kv_bits=8, impl="flash"))
    assert isinstance(b, eng.Int8KVBackend)
    assert b.layout.quantized and b.layout.impl == "flash"
    out, _, summary = eng.serve(
        cfg, params, reqs,
        dataclasses.replace(ecfg, layout=CacheLayout(kv_bits=8)))
    assert summary["finished"] >= 1 and out
