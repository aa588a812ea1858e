"""Serving launcher: continuous-batching engine under simulated recsys load.

Default mode drives :mod:`repro.serving` — a fixed-slot continuous-batching
engine fed by the Poisson/bursty Zipfian traffic simulator — and reports
throughput plus p50/p95/p99 TTFT / per-token latency against SLO tiers.
The engine serves **every architecture family** through its family-backend
registry (uniform decoders, gemma ring buffers, jamba/rwkv6 recurrent
state, whisper cross-KV), and ``--kv int8`` composes with any KV-bearing
family:

  PYTHONPATH=src python -m repro.launch.serve --reduced
  PYTHONPATH=src python -m repro.launch.serve --reduced --arch deepseek-7b \\
      --slots 8 --requests 64 --rate 128 --process bursty --kv int8
  PYTHONPATH=src python -m repro.launch.serve --reduced --arch rwkv6-1.6b
  PYTHONPATH=src python -m repro.launch.serve --reduced --arch gemma3-1b \\
      --kv int8

``--decode-impl flash`` swaps the decode-attention hot path for the Pallas
flash-decode kernel (per-slot length-aware KV-block skipping); ``--prefill-
chunk N`` streams uniform-family prompts through prefill in fixed chunks:

  PYTHONPATH=src python -m repro.launch.serve --reduced --arch gemma3-1b \\
      --decode-impl flash
  PYTHONPATH=src python -m repro.launch.serve --reduced --arch olmo-1b \\
      --decode-impl flash --prefill-chunk 8 --kv int8

``--cache-layout paged`` switches the KV cache to the shared block pool
with prefix sharing and copy-on-write (``--block-size`` rows per block,
``--num-blocks`` to cap the pool below the dense footprint,
``--no-prefix-sharing`` to disable prompt dedup).  All the cache knobs —
paging, int8, decode impl — are one :class:`repro.cache_layout.CacheLayout`
under the hood:

  PYTHONPATH=src python -m repro.launch.serve --reduced --arch olmo-1b \\
      --cache-layout paged --block-size 16 --decode-impl flash
  PYTHONPATH=src python -m repro.launch.serve --reduced --arch gemma3-1b \\
      --cache-layout paged --kv int8

``--spec-k N`` turns on speculative multi-token decode: each scheduler
step self-drafts up to ``N - 1`` continuation tokens per greedy slot
(``--spec-draft ngram`` — no second model) and verifies all rows in one
fused k-row decode, emitting the accepted prefix.  Token streams are
identical to single-step greedy decode; recurrent families (jamba,
rwkv6) reject the flag with a clear error:

  PYTHONPATH=src python -m repro.launch.serve --reduced --arch olmo-1b \\
      --spec-k 4 --cache-layout paged --decode-impl flash

``--disagg`` splits serving into a prefill tier and a decode tier
(requires ``--cache-layout paged`` — the KV handoff rides the block
pool) with ``--prefill-replicas`` / ``--decode-replicas`` engines per
tier and a router placing arrivals / handoffs by ``--router-policy``
(``slo`` scores load + live windowed p99, ``least_loaded``,
``round_robin``).  Token streams stay bit-identical to one interleaved
engine; ``--scenario prefill-burst`` drives the workload disaggregation
is for (long-prompt burst over decode-heavy background):

  PYTHONPATH=src python -m repro.launch.serve --reduced --arch olmo-1b \\
      --cache-layout paged --disagg --prefill-replicas 1 \\
      --decode-replicas 2 --scenario prefill-burst

``--candidates N`` attaches a head-heavy (Zipfian) candidate item set to
every request and ``--cf-plan`` mounts the sharded CF scoring head inside
the engine: each request is then a full retrieval->rank call — LM prefill
+ CF factor lookup + gated fusion + candidate ranking.  ``--cf-cache-rows``
sizes the frequency-tracked hot-row replica in front of the sharded
lookup (hits skip the cross-shard exchange; scores are bit-identical with
the cache on or off).  The CF head rides the single-engine path; with
``--disagg`` the flags are ignored (candidate scoring happens at prefill
admission, which disagg delegates to tier replicas):

  PYTHONPATH=src python -m repro.launch.serve --reduced --arch olmo-1b \\
      --candidates 16 --cf-plan row --cf-cache-rows 256

``--mode raw`` keeps the original fixed-batch decode-loop microbenchmark:

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \\
      --mode raw --batch 8 --new-tokens 32
"""
import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp

from repro.cache_layout import CacheLayout
from repro.config import get_arch, list_archs, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as tf
from repro.models.transformer import ModelCtx
from repro.obs import MetricsRegistry, Tracer, write_trace
from repro.serving import (CFHead, EngineConfig, PrefillBurstConfig,
                           RouterConfig, ServingEngine, TrafficConfig,
                           build_disagg, generate, generate_prefill_burst)
from repro.serving.engine import make_backend
from repro.serving.metrics import format_report


@dataclasses.dataclass
class EngineRun:
    """What one engine-mode run served: the workload, the measured
    engine, its outputs {rid: tokens} and summary, and host wall seconds of
    the warm-up run (it compiles every prefill bucket and the decode
    step)."""
    requests: list
    engine: object
    outputs: dict
    summary: dict
    warmup_s: float


def init_params(cfg, seed: int):
    """Random weights from ``seed``, built on the device in one program."""
    return jax.jit(tf.init_params, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)


def run_engine(args) -> EngineRun:
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(reduced(cfg), dtype="float32")
    params = init_params(cfg, args.seed)

    defaults = TrafficConfig()
    tcfg = TrafficConfig(
        n_requests=args.requests, rate=args.rate, process=args.process,
        prompt_max=max(defaults.prompt_min, min(48, args.max_len // 2)),
        new_tokens_max=max(defaults.new_tokens_min,
                           min(24, args.max_len // 4)),
        vocab_size=cfg.vocab_size, seed=args.seed,
        temperature=args.temperature, top_k=args.top_k,
        # recsys retrieval->rank: per-request candidate item sets (drawn
        # from a separate rng stream — the base workload is unperturbed)
        candidates=args.candidates,
        # enc-dec families: per-request encoder frames -> per-slot cross-KV
        encoder_frames=cfg.encoder_frames,
        frame_dim=cfg.d_model if cfg.encoder_layers else 0,
        # vlm (mrope): prompts carry an image-patch grid prefix so decode
        # exercises the text+patch position layout
        image_grid=(2, 2) if cfg.pos_type == "mrope" else ())
    if args.scenario == "prefill-burst":
        bcfg = PrefillBurstConfig(seed=args.seed)
        bcfg = dataclasses.replace(
            bcfg, background=dataclasses.replace(
                bcfg.background, vocab_size=cfg.vocab_size,
                seed=args.seed))
        requests = generate_prefill_burst(bcfg)
    else:
        requests = generate(tcfg)

    # every cache knob (paging, precision, decode impl) folds into one
    # CacheLayout; the legacy --kv/--decode-impl flags map onto it
    layout = CacheLayout(kind=args.cache_layout,
                         kv_bits=8 if args.kv == "int8" else 16,
                         impl=args.decode_impl,
                         block_size=args.block_size,
                         num_blocks=args.num_blocks,
                         prefix_sharing=not args.no_prefix_sharing)
    ecfg = EngineConfig(n_slots=args.slots, max_len=args.max_len,
                        queue_capacity=args.queue_capacity,
                        refill=args.refill, sample_seed=args.seed,
                        layout=layout, prefill_chunk=args.prefill_chunk,
                        spec_k=args.spec_k, spec_draft=args.spec_draft)
    try:
        rcfg = RouterConfig(policy=args.router_policy,
                            window=args.router_window,
                            ttft_weight=args.ttft_weight,
                            tpot_weight=args.tpot_weight)

        def mk_cf_head():
            if args.cf_plan == "off" or args.disagg:
                return None
            # trivial 1x1 mesh off-TPU: exercises the plan's shard_map
            # path; a real deployment hands in the training mesh
            mesh = make_host_mesh()
            return CFHead.build(
                n_users=tcfg.n_users, n_items=cfg.vocab_size, cf_dim=16,
                seed=args.seed, plan=args.cf_plan,
                cache_rows=args.cf_cache_rows, mesh=mesh)

        def mk_server(tracer=None, metrics=None):
            if args.disagg:
                return build_disagg(
                    cfg, params, n_prefill=args.prefill_replicas,
                    n_decode=args.decode_replicas, ecfg=ecfg,
                    router_cfg=rcfg, tracer=tracer, metrics=metrics)
            backend = make_backend(cfg, params, layout=layout,
                                   prefill_chunk=args.prefill_chunk)
            return ServingEngine(backend, ecfg, tracer=tracer,
                                 metrics=metrics, cf_head=mk_cf_head())

        t0 = time.perf_counter()
        if not args.no_warmup:
            # compile every prefill bucket + the decode step outside the
            # measured run, as a resident production server would be
            mk_server().run(requests)
        warmup_s = time.perf_counter() - t0
        # tracing is scoped to the measured run only, never the warmup
        tracer = Tracer() if args.trace_out else None
        metrics = MetricsRegistry() if args.trace_out else None
        engine = mk_server(tracer=tracer, metrics=metrics)
    except ValueError as e:       # layout/family/spec_k mismatches
        raise SystemExit(str(e))
    outputs, records, summary = engine.run(requests)
    if args.trace_out:
        n = write_trace(args.trace_out, tracer, metrics)
        print(f"trace: {n} events -> {args.trace_out} "
              f"(open at https://ui.perfetto.dev)")
    return EngineRun(requests, engine, outputs, summary, warmup_s)


def print_report(args, summary) -> None:
    topo = (f"disagg {args.prefill_replicas}P+{args.decode_replicas}D "
            f"{args.router_policy} " if args.disagg else "")
    title = (f"{args.arch} {topo}{args.cache_layout} kv={args.kv} "
             f"refill={args.refill} "
             f"slots={args.slots} {args.process}@{args.rate:g}req/s")
    print(format_report(summary, title))
    if "cf" in summary:
        s = summary["cf"]
        print(f"cf head: plan={s['plan']} scored={s['requests_scored']} "
              f"cache_rows={s['cache_rows']} (live {s['cache_rows_live']}) "
              f"hit_rate={s['hit_rate']:.3f} "
              f"({s['hits']} hits / {s['misses']} misses)")
    if args.json:
        print(json.dumps(summary, indent=1))


def run_raw(args) -> int:
    """Legacy fixed-batch decode loop (any family, incl. ssm/enc-dec)."""
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(reduced(cfg), dtype="float32")
    ctx = ModelCtx(attn_chunk=64, mamba_chunk=16, moe_group=64)
    params = init_params(cfg, args.seed)
    cache = tf.init_cache(cfg, args.batch, args.max_len)
    if cfg.encoder_layers:
        frames = jnp.zeros((args.batch, cfg.encoder_frames, cfg.d_model),
                           jnp.dtype(cfg.dtype))
        ck, cv = tf.whisper_prefill_cross(cfg, params, frames, ctx)
        cache["cross_k"], cache["cross_v"] = ck, cv

    decode = jax.jit(lambda p, c, t, pos=None: tf.decode_step(
        cfg, p, c, t, ctx, positions=pos))
    tok = jnp.ones((args.batch, 1), jnp.int32)
    pos = (jnp.zeros((args.batch, 1, 3), jnp.int32)
           if cfg.pos_type == "mrope" else None)

    # warmup + timed loop
    logits, cache = decode(params, cache, tok, pos)
    jax.block_until_ready(logits)
    t0 = time.perf_counter()
    for _ in range(args.new_tokens):
        logits, cache = decode(params, cache, tok, pos)
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
    jax.block_until_ready(logits)
    dt = time.perf_counter() - t0
    tps = args.batch * args.new_tokens / dt
    print(f"{cfg.name}: {tps:.1f} tokens/s ({jax.devices()[0].platform}), "
          f"{dt / args.new_tokens * 1e3:.1f} ms/step at batch {args.batch}")
    return 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mode", default="engine", choices=("engine", "raw"))
    # engine mode
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=64.0)
    ap.add_argument("--process", default="poisson",
                    choices=("poisson", "bursty"))
    ap.add_argument("--kv", default="native", choices=("native", "int8"))
    ap.add_argument("--cache-layout", default="dense",
                    choices=("dense", "paged"),
                    help="KV cache layout: dense per-slot (B, S, ...) rows "
                         "or the shared block pool with per-slot block "
                         "tables, prefix sharing and copy-on-write")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged layout: KV rows per physical block")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="paged layout: pool size in blocks (0 = auto: one "
                         "dense footprint, slots*max_len/block_size); set "
                         "below auto to oversubscribe and exercise "
                         "admission queueing")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="paged layout: disable content-hash prompt-prefix "
                         "block sharing")
    ap.add_argument("--decode-impl", default="dense",
                    choices=("dense", "flash"),
                    help="decode-attention hot path: dense XLA einsum over "
                         "the padded cache, or the Pallas flash-decode "
                         "kernel (per-slot length-aware KV-block skipping; "
                         "interpret mode off-TPU)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="stream uniform-family prompts through prefill in "
                         "fixed chunks of this many tokens (0 = monolithic "
                         "padded forward)")
    ap.add_argument("--spec-k", type=int, default=1,
                    help="speculative decode: verify up to this many token "
                         "rows per slot per step (1 = classic one-token "
                         "decode; KV families only — jamba/rwkv6 refuse)")
    ap.add_argument("--spec-draft", default="ngram", choices=("ngram",),
                    help="speculative draft source: self-speculative n-gram "
                         "lookup over the request's own prompt + output "
                         "(no second model)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated serving: a prefill tier hands "
                         "finished prompts' KV to a decode tier over the "
                         "block pool (requires --cache-layout paged); "
                         "token streams stay bit-identical to one "
                         "interleaved engine")
    ap.add_argument("--prefill-replicas", type=int, default=1,
                    help="disagg: engines in the prefill tier")
    ap.add_argument("--decode-replicas", type=int, default=1,
                    help="disagg: engines in the decode tier (0 = no "
                         "split; N 'both'-role replicas behind the "
                         "router)")
    ap.add_argument("--router-policy", default="slo",
                    choices=("slo", "least_loaded", "round_robin"),
                    help="replica placement: slo = normalized load + "
                         "windowed tail-latency percentile, least_loaded "
                         "= load only, round_robin = stateless")
    ap.add_argument("--router-window", type=int, default=64,
                    help="slo policy: recent latency samples per replica "
                         "feeding the windowed p99")
    ap.add_argument("--ttft-weight", type=float, default=1.0,
                    help="slo policy: weight of windowed p99 TTFT in the "
                         "prefill-placement score")
    ap.add_argument("--tpot-weight", type=float, default=10.0,
                    help="slo policy: weight of windowed p99 TPOT in the "
                         "decode-placement score")
    ap.add_argument("--scenario", default="traffic",
                    choices=("traffic", "prefill-burst"),
                    help="prefill-burst: seeded burst of long prompts "
                         "over a decode-heavy Zipfian background (the "
                         "disaggregation stress workload)")
    ap.add_argument("--candidates", type=int, default=0,
                    help="recsys retrieval->rank: head-heavy (Zipfian) "
                         "candidate item ids per request the CF head "
                         "scores and ranks (0 = plain LM serving)")
    ap.add_argument("--cf-plan", default="off",
                    choices=("off", "replicated", "row", "col", "row_col"),
                    help="mount the CF scoring head with its cf_user/"
                         "cf_item factor tables under this sharding plan "
                         "(single-engine mode only; ignored with --disagg)")
    ap.add_argument("--cf-cache-rows", type=int, default=128,
                    help="hot-row replica capacity per CF table: the "
                         "frequency-tracked head served without the "
                         "cross-shard exchange (0 = cache off; scores are "
                         "bit-identical either way)")
    ap.add_argument("--refill", default="continuous",
                    choices=("continuous", "static"))
    ap.add_argument("--queue-capacity", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k best logits (0 = off)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, the traffic and "
                         "sampling")
    ap.add_argument("--no-warmup", action="store_true")
    ap.add_argument("--trace-out", default="",
                    help="write the measured run's span timeline + metrics "
                         "here: .jsonl for raw events, anything else for "
                         "Chrome-trace/Perfetto JSON")
    ap.add_argument("--json", action="store_true")
    # raw mode
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=32)
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    if args.mode == "raw":
        return run_raw(args)
    print_report(args, run_engine(args).summary)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
