"""Bring-up check: the serving engine and the trainer on a TPU, full width.

    python chip_smoke.py               # one chip
    python chip_smoke.py --multichip   # one host with four chips

One chip runs three phases in one process, through the functions the
launchers call (``repro.launch.serve.run_engine``,
``repro.launch.train.run``), with random weights from ``--seed``:

1. flash-decode vs the dense XLA path at olmo-1b widths (B=8, S=1024,
   H=Hk=16, D=128, bf16) for the dense, sliding-window ring, paged and
   paged int8 caches: max |flash - dense| <= DECODE_RTOL * max |dense|;
2. olmo-1b (16 layers, d_model 2048, vocab 50304, bf16) served through
   the paged, flash-decode engine with the CF head under the ``row`` plan:
   every request must finish with its full token count and every
   candidate set must be scored with finite values;
3. recllm-base (the paper's backbone) through the GSPMD train step on a
   1x1 mesh, batch 16 x seq 512: every loss must be finite.

``--multichip`` runs only what exists across chips:

1. olmo-1b's GSPMD hybrid step on data=2 x model=2 at the published depth;
2. the same step and the pipelined step (2 stages over data=2, top-k
   compressed DP sync, kernels native) at ``MULTICHIP_LAYERS`` layers on
   one seed and batch: their step-0 losses agree within LOSS_RTOL;
3. the CF head under the ``row`` plan over four devices scores bit-exactly
   as under ``replicated``.

The script needs the TPU: with no chip, or away from the repository, it
exits non-zero and prints no result.  Times printed are set-up (they
include compilation), not performance results.  The last line is the JSON
result.
"""
import argparse
import json
import math
import os
import pathlib
import sys
import time

DECODE_RTOL = 1e-2       # bf16 output rounding is ~4e-3 relative
LOSS_RTOL = 1e-2         # one loss, two partitionings of the same bf16 math
MULTICHIP_LAYERS = 8     # the pipelined step's state fits 16 GB up to here

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))


def require_tpu():
    """Pin JAX to the TPU before any device is touched: no chip is an
    error, never a CPU run."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "tpu" not in plats.split(","):
        raise SystemExit(f"chip_smoke: JAX_PLATFORMS={plats!r} excludes "
                         f"the TPU")
    import jax
    jax.config.update("jax_platforms", "tpu")
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU, found {devs[0].platform}")
    return devs


def peak_bytes(jax) -> int:
    return max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def decode_phase():
    """Flash-decode vs dense XLA decode attention at olmo-1b widths."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.cache_layout import CacheLayout
    from repro.kernels import ops

    B, S, H, D, bs = 8, 1024, 16, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.bfloat16)
    lengths = jnp.array([0, 1, 17, 128, 300, 513, 1000, 1024], jnp.int32)
    # paged: every slot's blocks scattered over a shuffled pool
    nb = S // bs
    perm = jax.random.permutation(ks[3], B * nb).astype(jnp.int32)
    table = perm.reshape(B, nb)
    inv = jnp.argsort(perm)

    def pool(x):                              # (B, S, ...) -> (N, bs, ...)
        blocks = x.reshape((B * nb, bs) + x.shape[2:])
        return blocks[inv]

    k_q = jax.random.randint(ks[4], (B, S, H, D), -127, 128, jnp.int8)
    v_q = jax.random.randint(ks[5], (B, S, H, D), -127, 128, jnp.int8)
    k_s = jax.random.uniform(ks[6], (B, S, H), jnp.float32, 1e-3, 2e-2)
    v_s = jax.random.uniform(ks[7], (B, S, H), jnp.float32, 1e-3, 2e-2)
    cases = {
        "dense": (CacheLayout(), {"k": k, "v": v}),
        "window_ring": (CacheLayout(window=256, ring=True),
                        {"k": k, "v": v}),
        "paged": (CacheLayout(kind="paged"),
                  {"k": pool(k), "v": pool(v), "block_table": table}),
        "paged_int8": (CacheLayout(kind="paged", kv_bits=8),
                       {"k_q": pool(k_q), "k_s": pool(k_s),
                        "v_q": pool(v_q), "v_s": pool(v_s),
                        "block_table": table}),
    }
    for name, (layout, cache) in cases.items():
        outs = {}
        for impl in ("flash", "dense"):
            t0 = time.perf_counter()
            outs[impl] = np.asarray(ops.decode_attention(
                q, cache, lengths, layout=layout.replace(impl=impl)),
                np.float32)
            dt = time.perf_counter() - t0
        ref = np.abs(outs["dense"]).max()
        err = np.abs(outs["flash"] - outs["dense"]).max()
        print(f"decode {name}: max|flash-dense| {err:.3e} vs max|dense| "
              f"{ref:.3e} (rtol {DECODE_RTOL}); dense call incl. compile "
              f"{dt:.1f}s")
        assert np.isfinite(outs["flash"]).all(), name
        assert err <= DECODE_RTOL * ref, (name, err, ref)
        # empty slots attend nothing: exactly zero
        assert not outs["flash"][0].any(), name


def serve_phase(seed: int):
    """olmo-1b at full width through the engine: paged + flash + CF."""
    import numpy as np
    from repro.launch import serve

    argv = ["--arch", "olmo-1b", "--cache-layout", "paged",
            "--decode-impl", "flash", "--candidates", "16", "--cf-plan",
            "row", "--requests", "8", "--slots", "4", "--max-len", "512",
            "--seed", str(seed)]
    print("serve:", " ".join(argv))
    res = serve.run_engine(serve.build_parser().parse_args(argv))
    eng = res.engine
    print(f"serve: olmo-1b {eng.backend.cfg.num_params() / 1e6:.1f}M params")
    want = {r.rid: r.max_new_tokens for r in res.requests}
    got = {rid: len(toks) for rid, toks in res.outputs.items()}
    print(f"serve: {len(got)}/{len(want)} requests, "
          f"{sum(got.values())} tokens produced (want "
          f"{sum(want.values())}), {res.summary['decode_steps']} decode "
          f"steps; warm-up run incl. compile {res.warmup_s:.1f}s")
    assert got == want, (got, want)
    scored = eng.cf_results
    assert sorted(scored) == sorted(want), sorted(scored)
    for r in res.requests:
        out = scored[r.rid]
        assert len(out["fused"]) == len(r.candidates), r.rid
        assert np.isfinite(out["fused"]).all(), r.rid
        assert sorted(out["ranking"]) == sorted(r.candidates), r.rid
    print(f"serve: {len(scored)} candidate sets scored, "
          f"cf plan {res.summary['cf']['plan']}")


def train_phase(argv, label: str):
    from repro.launch import train

    print(f"{label}:", " ".join(argv))
    out = train.run(train.build_parser().parse_args(argv))
    print(f"{label}: losses {out.losses}; first step incl. compile "
          f"{out.step_seconds[0]:.1f}s")
    assert out.steps_run == int(argv[argv.index("--steps") + 1])
    assert all(math.isfinite(x) for x in out.losses), out.losses
    return out.losses


def cf_phase(seed: int):
    """CF head: row plan over four devices == replicated, bit for bit."""
    import numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.serving import CFHead

    rng = np.random.default_rng(seed)
    users = rng.integers(0, 10_000, 32)
    cands = rng.integers(0, 50_304, (32, 16))
    lm = rng.standard_normal((32, 50_304)).astype(np.float32)
    scores = {}
    for plan, mesh in (("replicated", make_host_mesh()),
                       ("row", make_host_mesh(data=1, model=4))):
        for cache_rows in (0, 128):
            head = CFHead.build(n_users=10_000, n_items=50_304, cf_dim=64,
                                seed=seed, plan=plan, mesh=mesh,
                                cache_rows=cache_rows)
            scores[plan, cache_rows] = [
                head.score(int(u), c, lm_logits_row=row)
                for u, c, row in zip(users, cands, lm)]
    base = scores["replicated", 0]
    for key, outs in scores.items():
        for a, b in zip(base, outs):
            assert np.array_equal(a["cf"], b["cf"]), key
            assert np.array_equal(a["fused"], b["fused"]), key
    print(f"cf: row plan over 4 devices bit-exact vs replicated on "
          f"{len(base)} requests (hot-row cache off and 128 rows)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--multichip", action="store_true",
                    help="run only the four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu()
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
          f"compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()

    def phase(label, fn, *a):
        t = time.perf_counter()
        out = fn(*a)
        print(f"{label}: passed in {time.perf_counter() - t:.1f}s (set-up "
              f"and compilation included); peak HBM per chip "
              f"{peak_bytes(jax) / 2**30:.2f} GiB")
        return out

    if args.multichip:
        if len(devs) != 4:
            raise SystemExit(f"--multichip needs 4 chips, found {len(devs)}")
        common = ["--arch", "olmo-1b", "--steps", "1", "--batch", "8",
                  "--seq", "512", "--seed", str(args.seed)]
        phase("hybrid 2x2 full depth", train_phase,
              common + ["--data", "2", "--model", "2"],
              "train olmo-1b hybrid 2x2 full depth")
        cut = common + ["--layers", str(MULTICHIP_LAYERS)]
        l_h = phase("hybrid 2x2 cut", train_phase,
                    cut + ["--data", "2", "--model", "2"],
                    f"train olmo-1b hybrid 2x2 {MULTICHIP_LAYERS}L")
        l_p = phase("pipelined cut", train_phase,
                    cut + ["--data", "2", "--pp-stages", "2", "--pp-micro",
                           "2", "--grad-sync", "topk"],
                    f"train olmo-1b pipelined 2 stages x data 2 top-k "
                    f"sync {MULTICHIP_LAYERS}L")
        rel = abs(l_h[0] - l_p[0]) / abs(l_h[0])
        print(f"step-0 loss hybrid {l_h[0]:.6f} vs pipelined {l_p[0]:.6f}: "
              f"rel diff {rel:.2e} (rtol {LOSS_RTOL})")
        assert rel <= LOSS_RTOL, (l_h[0], l_p[0])
        phase("cf row vs replicated", cf_phase, args.seed)
    else:
        phase("decode", decode_phase)
        phase("serve", serve_phase, args.seed)
        phase("train", train_phase,
              ["--arch", "recllm-base", "--steps", "3", "--batch", "16",
               "--seq", "512", "--seed", str(args.seed)],
              "train recllm-base 1x1")
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s (set-up "
          f"and compilation included)")
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
