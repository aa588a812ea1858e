"""Record a small profiler trace on four chips, for the test of the
collectives' reader.

    python bench/tools/record_collective_trace.py OUT_DIR

Over a 2x2 mesh of the host's four chips, a jitted step (a matmul whose
contraction is split over the chips, so its partial sums are all-reduced,
then the rows gathered whole) runs three times under ``bench.step`` host
annotations; the trace is copied to ``OUT_DIR/collectives.xplane.pb`` and
the name and time of each chip's operations printed.  Needs four TPUs.
"""
import glob
import json
import os
import shutil
import sys
import tempfile


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import profiler
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < 4:
        raise SystemExit("record_collective_trace: needs four TPUs")
    mesh = Mesh(np.asarray(devs[:4]).reshape(2, 2), ("data", "model"))
    a = jax.device_put(jnp.ones((2048, 4096), jnp.bfloat16),
                       NamedSharding(mesh, P("data", "model")))
    b = jax.device_put(jnp.ones((4096, 2048), jnp.bfloat16),
                       NamedSharding(mesh, P("model", None)))

    @jax.jit
    def step(a, b):
        y = jax.lax.with_sharding_constraint(
            a @ b, NamedSharding(mesh, P("data", None)))
        return jax.lax.with_sharding_constraint(
            jnp.tanh(y), NamedSharding(mesh, P()))

    step(a, b).block_until_ready()
    tmp = tempfile.mkdtemp()
    profiler.start_trace(tmp)
    for _ in range(3):
        with profiler.TraceAnnotation("bench.step"):
            step(a, b).block_until_ready()
    profiler.stop_trace()
    path = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")[0]
    os.makedirs(out, exist_ok=True)
    shutil.copy(path, os.path.join(out, "collectives.xplane.pb"))
    pd = profiler.ProfileData.from_file(path)
    for pl in pd.planes:
        if pl.name.startswith("/device:TPU:"):
            for ln in pl.lines:
                print(json.dumps([pl.name, ln.name, [
                    [e.name[:60], e.duration_ns] for e in ln.events][:40]]))
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
