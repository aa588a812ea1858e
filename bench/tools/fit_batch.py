"""Size a training cell's batch by what the TPU compiler says it needs.

    JAX_PLATFORMS=cpu python bench/tools/fit_batch.py <config> <job> B [B ...]

``<job>`` is a traffic name under ``bench/traffic`` or a path to a job
file.

Compiles the cell's train step, without a chip, for a described TPU v5e
(one chip, or a 2x2 host when the job's data x model is 4) at each batch
size and prints ``memory_analysis()`` per chip: arguments, outputs,
temporaries.  Run it on the CPU; it never touches a device.
"""
import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(config: str, job: str, batches) -> int:
    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from benchlib import weights
    from benchlib.program import program_config
    from repro.config import ParallelConfig, ShapeConfig, TrainConfig
    from repro.core.hybrid import auto_plan
    from repro.optimizer import adamw
    from repro.runtime import trainer

    jax.config.update("jax_enable_compilation_cache", False)
    c = json.load(open(HERE / "configs" / f"{config}.json"))
    j = json.load(open(job if job.endswith(".json")
                       else HERE / "traffic" / f"{job}.json"))
    cfg = program_config(c)
    n = j["data"] * j["model"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:n]).reshape(j["data"], j["model"]),
                ("data", "model"))
    for B in batches:
        shape = ShapeConfig("bench", j["seq"], B, "train")
        plan = auto_plan(cfg, mesh, shape, ParallelConfig(
            dp=j["data"], tp=j["model"], pp=1,
            microbatches=j["microbatches"]))
        _, jitted, sh_for = trainer.make_hybrid_train_step(
            cfg, plan, TrainConfig(steps=j["schedule_steps"]))
        ps = weights.shapes(c)
        os_ = jax.eval_shape(adamw.init_opt_state, ps)
        bs = {"tokens": jax.ShapeDtypeStruct((B, j["seq"]), np.int32),
              "targets": jax.ShapeDtypeStruct((B, j["seq"]), np.int32),
              "mask": jax.ShapeDtypeStruct((B, j["seq"]), np.float32)}
        psh, osh, bsh = sh_for(ps, bs)
        put = lambda t, s: jax.tree.map(  # noqa: E731
            lambda x, y: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=y),
            t, s)
        comp = jitted(ps, bs).lower(put(ps, psh), put(os_, osh),
                                    put(bs, bsh)).compile()
        m = comp.memory_analysis()
        gb = 1e9
        print(f"batch {B}: args {m.argument_size_in_bytes / gb:.2f} GB, "
              f"out {m.output_size_in_bytes / gb:.2f} GB, temp "
              f"{m.temp_size_in_bytes / gb:.2f} GB, alias "
              f"{m.alias_size_in_bytes / gb:.2f} GB; plan {plan.notes}",
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1], sys.argv[2],
                          [int(b) for b in sys.argv[3:]]))
