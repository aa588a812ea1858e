"""Record a small profiler trace of the serving engine on the chip, for the
test of the engine-span reduction.

    python bench/tools/record_serve_trace.py OUT_DIR

A tiny olmo-shaped decoder (2 layers, width 256, two heads of 128) served
by the paged flash-decode engine with a CF head, with an enabled
``repro.obs.trace.Tracer``.  After a warm-up pass over the same shapes,
a few ticks run under the profiler, each inside a ``bench.tick``
annotation as the benchmark's window drives them.  Copies the
``.xplane.pb`` to ``OUT_DIR/serve_spans.xplane.pb`` and prints the
device operations and the idle split by span.  Needs the TPU.
"""
import dataclasses
import glob
import json
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT / "bench")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def requests(rng, n: int, vocab: int, first_rid: int):
    from repro.serving.traffic import Request
    return [Request(rid=first_rid + i, user_id=i % 8,
                    prompt=tuple(int(t) for t in rng.integers(3, vocab,
                                                              12 + 20 * i)),
                    max_new_tokens=4, arrival=0.0,
                    candidates=tuple(int(c) for c in
                                     rng.choice(vocab, 16, replace=False)))
            for i in range(n)]


def serve(engine, reqs, annotate: bool) -> None:
    from jax.profiler import TraceAnnotation
    for r in reqs:
        engine.submit(r)
    while engine.has_work:
        if annotate:
            with TraceAnnotation("bench.tick"):
                engine.tick()
        else:
            engine.tick()


def main(out: str) -> int:
    import jax
    import numpy as np
    from jax import profiler

    from benchlib import engine_trace, trace
    from repro.cache_layout import CacheLayout
    from repro.config import get_arch
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tf
    from repro.obs import Tracer
    from repro.serving import CFHead, EngineConfig, ServingEngine
    from repro.serving.engine import make_backend

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_serve_trace: no TPU")
    cfg = dataclasses.replace(get_arch("olmo-1b"), num_layers=2, d_model=256,
                              num_heads=2, num_kv_heads=2, head_dim=128,
                              d_ff=512, vocab_size=1024)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)
    layout = CacheLayout(kind="paged", impl="flash", block_size=16)
    backend = make_backend(cfg, params, layout=layout)
    ecfg = EngineConfig(n_slots=4, max_len=128, prompt_quantum=16,
                        layout=layout)
    head = CFHead.build(n_users=8, n_items=cfg.vocab_size, cf_dim=64,
                        plan="row", cache_rows=32, mesh=make_host_mesh())
    rng = np.random.default_rng(0)
    serve(ServingEngine(backend, ecfg, cf_head=head),
          requests(rng, 6, cfg.vocab_size, 0), annotate=False)
    engine = ServingEngine(backend, ecfg, cf_head=head, tracer=Tracer())
    tmp = tempfile.mkdtemp()
    profiler.start_trace(tmp)
    serve(engine, requests(rng, 6, cfg.vocab_size, 6), annotate=True)
    profiler.stop_trace()
    path = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0]
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "serve_spans.xplane.pb"))
    pd = profiler.ProfileData.from_file(path)
    red = trace.reduce_profile(pd)
    print(json.dumps({"ticks": engine.ticks, "host_syncs": engine.host_syncs,
                      "dropped": engine.tracer.dropped,
                      "window_s": red.window_s, "busy_s": red.busy_s,
                      "ops": red.top_ops(20), "gaps": red.top_gaps(),
                      "program_gaps": sorted(
                          trace.reduce_profile(
                              pd, prefix=engine_trace.PREFIX).gaps_s.items(),
                          key=lambda kv: -kv[1])}))
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
