"""Record a small profiler trace on the chip, for the trace-reduction test.

    python bench/tools/record_trace.py OUT_DIR

Runs a jitted matmul and the paged flash-decode kernel a few times under
``bench.*`` host annotations, traces them, copies the ``.xplane.pb`` to
``OUT_DIR/small.xplane.pb`` and writes ``OUT_DIR/structure.json``: every
plane, its lines, and the first events of each line.  Needs the TPU.
"""
import glob
import json
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax import profiler

    from repro.cache_layout import CacheLayout
    from repro.kernels import ops

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: no TPU")
    mm = jax.jit(lambda a, b: a @ b)
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    B, H, D, bs, nb = 4, 16, 128, 16, 8
    q = jnp.ones((B, 1, H, D), jnp.bfloat16)
    pool = jnp.ones((B * nb + 1, bs, H, D), jnp.bfloat16)
    table = (1 + jnp.arange(B * nb, dtype=jnp.int32)).reshape(B, nb)
    lengths = jnp.array([5, 40, 100, 128], jnp.int32)
    layout = CacheLayout(kind="paged", impl="flash", block_size=bs)

    def decode():
        return ops.decode_attention(
            q, {"k": pool, "v": pool, "block_table": table}, lengths,
            layout=layout)

    mm(a, a).block_until_ready()
    decode().block_until_ready()
    tmp = tempfile.mkdtemp()
    profiler.start_trace(tmp)
    for _ in range(3):
        with profiler.TraceAnnotation("bench.matmul"):
            mm(a, a).block_until_ready()
        with profiler.TraceAnnotation("bench.decode"):
            decode().block_until_ready()
    profiler.stop_trace()
    path = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0]
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "small.xplane.pb"))
    pd = profiler.ProfileData.from_file(path)
    desc = []
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            evs = list(ln.events)
            lines.append({"name": ln.name, "n": len(evs), "first": [
                [e.name, e.start_ns, e.duration_ns,
                 [[k, str(v)] for k, v in e.stats]] for e in evs[:6]]})
        desc.append({"plane": pl.name, "lines": lines})
    with open(os.path.join(out, "structure.json"), "w") as f:
        json.dump(desc, f, indent=1)
    print(json.dumps([[d["plane"], [(l["name"], l["n"]) for l in d["lines"]]]
                      for d in desc]))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
