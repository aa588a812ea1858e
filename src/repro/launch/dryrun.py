import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST come before any jax import: jax locks the platform and the device
# count on first init.
# The production dry-run needs 512 placeholder devices for the 2x16x16 mesh.

# HLO dump (still before any jax import): the roofline reads the post-SPMD,
# pre-float-normalization module — per-device shapes with bf16 preserved
# (XLA:CPU promotes bf16->f32 later; TPU would not).
import tempfile  # noqa: E402
_DUMP_DIR = os.environ.get("REPRO_DUMP_DIR") or tempfile.mkdtemp(
    prefix="repro_hlo_dump_")
os.environ["XLA_FLAGS"] += (
    f" --xla_dump_to={_DUMP_DIR}"
    " --xla_dump_hlo_pass_re=all-reduce-promotion"
    " --xla_dump_large_constants=false")

"""Multi-pod dry-run launcher (deliverable e).

For every (architecture x input shape) cell, lower + compile the appropriate
step (train_step / prefill_step / serve_step) against the production mesh —
16x16=256 chips single-pod and 2x16x16=512 chips multi-pod — and record
memory_analysis / cost_analysis / collective traffic for the roofline.

  PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --out experiments/dryrun

``--all`` runs each cell in a fresh subprocess (cell isolation: one cell's
compiler crash or memory blow-up cannot take down the sweep — the same
fault-tolerance stance the trainer takes toward nodes).
"""
import argparse      # noqa: E402
import json          # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402


def _print_result(res: dict, dt: float) -> None:
    arch, shape, mesh_name = res["arch"], res["shape"], res["mesh"]
    if res["status"] == "ok":
        rl, mem = res["roofline"], res["memory"]
        print(f"[ok {dt:6.1f}s] {arch} x {shape} x {mesh_name}: "
              f"compute {rl['t_compute']*1e3:.1f}ms "
              f"memory {rl['t_memory']*1e3:.1f}ms "
              f"coll {rl['t_collective']*1e3:.1f}ms "
              f"-> {rl['bottleneck']}; "
              f"peak~{mem['peak_bf16adj_gb']:.2f}GB/dev "
              f"fits={mem['fits_16g']}", flush=True)
    elif res["status"] == "skipped":
        print(f"[skip   ] {arch} x {shape} x {mesh_name}: {res['notes'][0]}",
              flush=True)
    else:
        print(f"[ERROR {dt:5.1f}s] {arch} x {shape} x {mesh_name}:\n"
              f"{res['error']}", flush=True)


def run_one(arch: str, shape: str, multi_pod: bool, out_dir: str,
            force: bool = False, keep_hlo: bool = False) -> dict:
    from repro.launch import dryrun_lib
    from repro.launch.mesh import make_production_mesh
    mesh_name = "2x16x16" if multi_pod else "16x16"
    path = dryrun_lib.result_path(out_dir, arch, shape, mesh_name)
    if not force and os.path.exists(path):
        with open(path) as f:
            cached = json.load(f)
        if cached.get("status") in ("ok", "skipped"):
            print(f"[cached ] {arch} x {shape} x {mesh_name}: "
                  f"{cached['status']}", flush=True)
            return cached
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    res = dryrun_lib.run_cell(
        arch, shape, mesh, mesh_name,
        keep_hlo_dir=os.path.join(out_dir, "hlo") if keep_hlo else None,
        dump_dir=_DUMP_DIR)
    dt = time.perf_counter() - t0
    dryrun_lib.save_result(res, out_dir)
    _print_result(res.to_dict(), dt)
    return res.to_dict()


def run_all_subprocess(out_dir: str, force: bool, keep_hlo: bool,
                       timeout_s: int = 3000) -> int:
    """One subprocess per cell (isolation + fresh dump dir + fresh XLA)."""
    from repro.config import SHAPES, list_archs
    archs = tuple(a for a in list_archs() if a != "recllm-base")
    failures = 0
    for arch in archs:
        for shape in SHAPES:
            for flag in ([], ["--multi-pod"]):
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--out", out_dir] \
                    + flag + (["--force"] if force else []) \
                    + (["--keep-hlo"] if keep_hlo else [])
                env = dict(os.environ)
                env.pop("REPRO_DUMP_DIR", None)
                env.pop("XLA_FLAGS", None)
                try:
                    p = subprocess.run(cmd, env=env, timeout=timeout_s,
                                       cwd=os.getcwd())
                    failures += p.returncode != 0
                except subprocess.TimeoutExpired:
                    print(f"[TIMEOUT] {arch} x {shape} "
                          f"{'multi' if flag else 'single'}-pod", flush=True)
                    failures += 1
    return failures


def main(argv=None) -> int:
    from repro.config import SHAPES, list_archs
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=tuple(list_archs()))
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch x shape x mesh) cell, subprocess each")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--keep-hlo", action="store_true")
    args = ap.parse_args(argv)

    if args.all:
        failures = run_all_subprocess(args.out, args.force, args.keep_hlo)
        print(f"done; {failures} failures")
        return 1 if failures else 0

    if not (args.arch and args.shape):
        ap.error("--arch and --shape required unless --all")
    res = run_one(args.arch, args.shape, args.multi_pod, args.out,
                  force=args.force, keep_hlo=args.keep_hlo)
    return 1 if res["status"] == "error" else 0


if __name__ == "__main__":
    sys.exit(main())
