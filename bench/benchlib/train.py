"""Training cells: the program's GSPMD train step on packed item tokens.

Set-up builds one object, the compiled step (``runtime/trainer.py``'s
hybrid step under the plan ``core/hybrid.auto_plan`` gives, as
``launch/train.py`` builds it) with its sharded state, from the bench's
weights, and drives it through its first three steps with the window's
own feed.  It keeps what the check compares: each step's loss, the norm of
each leaf's first gradient as the optimizer got it (read back from the
first moment after one step), and the norm of each leaf's change over the
three steps (read from the master weights that step 4 receives).  The
window then goes on with the same object.

The reference follows the same three steps in float32 at
``Precision.HIGHEST``, in blocks of rows, with AdamW written out plainly.
On several chips its state is spread over the cell's own chips, each leaf
split along one axis (``reference_placement``): plain ``jax.numpy`` still,
nothing of the program's plan, and it fits where one chip would not.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import sys
import time
from typing import Deque, Dict, List, Optional

import numpy as np

from benchlib import gen, weights
from benchlib.program import check_layout, program_config
from benchlib.runlog import Run, Spans


@dataclasses.dataclass
class Train:
    cfg: object
    c: dict
    job: dict
    seed: int
    fn: object
    params: object
    opt: object
    feed: object
    place: object
    psh: object
    devices: List = dataclasses.field(default_factory=list)
    batches: List[Dict[str, np.ndarray]] = dataclasses.field(
        default_factory=list)


def build(c: dict, job: dict, seed: int) -> Train:
    import jax
    from repro.config import ParallelConfig, ShapeConfig, TrainConfig
    from repro.core.hybrid import auto_plan
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as tf
    from repro.optimizer import adamw
    from repro.runtime import trainer

    cfg = program_config(c)
    B, S = job["batch"], job["seq"]
    mesh = make_host_mesh(data=job["data"], model=job["model"])
    shape = ShapeConfig("bench", S, B, "train")
    pcfg = ParallelConfig(dp=job["data"], tp=job["model"], pp=1,
                          microbatches=job["microbatches"])
    plan = auto_plan(cfg, mesh, shape, pcfg)
    tcfg = TrainConfig(steps=job["schedule_steps"],
                       learning_rate=job["learning_rate"],
                       warmup_steps=job["warmup_steps"],
                       weight_decay=job["weight_decay"], b1=job["b1"],
                       b2=job["b2"], eps=job["eps"],
                       grad_clip=job["grad_clip"])
    _, jitted, shardings_for = trainer.make_hybrid_train_step(cfg, plan,
                                                             tcfg)
    feed = gen.train_rows(job, seed, c["vocab_size"], B)
    first = next(feed)
    params_shape = jax.eval_shape(
        lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    batch_shape = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for k, v in first.items()}
    psh, osh, bsh = shardings_for(params_shape, batch_shape)
    params = weights.make_params(c, seed, out_shardings=psh)
    check_layout(cfg, params)
    opt = jax.jit(adamw.init_opt_state, out_shardings=osh)(params)
    fn = jitted(params_shape, batch_shape)

    def place(b):
        return jax.tree.map(lambda x, sh: jax.device_put(x, sh), b, bsh)

    def rows():
        yield first
        yield from feed

    return Train(cfg, c, job, seed, fn, params, opt, rows(), place, psh,
                 list(mesh.devices.flat))


def _leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for p, x in flat}


def first_steps(t: Train) -> Dict:
    """Steps 1-3 through the window's own call and feed; what the check
    compares is read from the state as it goes."""
    import jax
    import jax.numpy as jnp
    losses = []
    grad = change = None
    norms = jax.jit(_leaf_norms)
    b1 = t.job["b1"]
    for i in range(3):
        b = next(t.feed)
        t.batches.append(b)
        t.params, t.opt, m = t.fn(t.params, t.opt, t.place(b))
        losses.append(m["loss"])
        if i == 0:
            grad = norms(t.opt["m"])
            first = _leaves(t.opt["m"], 1.0 / (1.0 - b1))
    master0 = weights.make_params(t.c, t.seed, out_shardings=t.psh)

    @jax.jit
    def delta(master, p0):
        return _leaf_norms(jax.tree.map(
            lambda a, b: a - b.astype(jnp.float32), master, p0))

    change = delta(t.opt["master"], master0)
    del master0
    out = {"loss": [float(x) for x in losses],
           "grad": {k: float(v) / (1.0 - b1) for k, v in grad.items()},
           "change": {k: float(v) for k, v in change.items()},
           "grad_vec": first}
    return out


def _leaves(tree, scale: float = 1.0) -> Dict[str, np.ndarray]:
    """Each leaf on the host as float32, by its path."""
    import jax
    return {jax.tree_util.keystr(p): np.asarray(x, np.float32) * scale
            for p, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


@dataclasses.dataclass
class Window:
    steps: int
    elapsed: float
    tokens: int
    failed: int
    compiles: int


def run_window(t: Train, seconds: float, spans: Optional[Spans] = None,
               counter=None, trace=None) -> Window:
    """Steps back to back for ``seconds``, ``ahead_steps`` of them
    dispatched ahead of the one the host waits for, so that the chip runs
    on through a stall of the host; each loss is read that many steps
    late.  When the time is up nothing more is sent, every step sent is
    waited for, and the clock is read after that wait: all of that work
    counts, over all of that time.  The loop is the same traced or not:
    the step time read is the interval between two steps' ends as the
    host waits for them in order."""
    B, S = t.job["batch"], t.job["seq"]
    ahead = t.job["ahead_steps"]
    sp = spans if spans is not None else Spans(enabled=False)
    steps = failed = 0
    pending: Deque = collections.deque()
    done = None
    gaps: List[float] = []
    pause = 0.0     # the longest the host spent away from waiting

    def finish():
        nonlocal steps, failed, done
        with sp.span("bench.wait"):
            failed += not np.isfinite(float(pending.popleft()))
        steps += 1
        now = time.perf_counter()
        if done is not None:
            gaps.append(now - done)
        done = now
        return now

    gc.collect()
    gc.freeze()     # set-up's objects: no collection in the window scans them
    gc0 = gc.get_stats()[2]["collections"]
    if counter is not None:
        counter.on = True
    if trace is not None:
        trace.start()
    t0 = back = time.perf_counter()
    while True:
        with sp.span("bench.feed"):
            batch = t.place(next(t.feed))
        with sp.span("bench.dispatch"):
            t.params, t.opt, m = t.fn(t.params, t.opt, batch)
        pending.append(m["loss"])
        if len(pending) > ahead:
            now = time.perf_counter()
            pause = max(pause, now - back)
            back = finish()
        if time.perf_counter() - t0 >= seconds:
            break
    while pending:
        finish()
    elapsed = time.perf_counter() - t0
    if counter is not None:
        counter.on = False
    if trace is not None:
        trace.stop()
    gc.unfreeze()
    if spans is not None:
        spans.durations["train.step"] = gaps
    print(f"window: {counter.count if counter else 0} programs compiled, "
          f"{gc.get_stats()[2]['collections'] - gc0} full collections, "
          f"{steps} steps ({ahead} ahead), {elapsed:.3f} s, longest step "
          f"{max(gaps, default=0) * 1e3:.1f} ms, longest host pause "
          f"{pause * 1e3:.1f} ms, first steps "
          f"{[round(g * 1e3, 1) for g in gaps[:4]]} ms", file=sys.stderr)
    return Window(steps=steps, elapsed=elapsed, tokens=steps * B * S,
                  failed=failed, compiles=counter.count if counter else 0)


def fill_run(run: Run, t: Train, win: Window) -> None:
    from benchlib import costs
    run.counters["train.window_compiles"] = win.compiles
    run.counters["window_s"] = win.elapsed
    run.counters["flops"] = win.steps * costs.train_flops(
        t.c, t.job["batch"], t.job["seq"])


# -- the reference -------------------------------------------------------

def _schedule(step: int, job: dict) -> float:
    """Linear warm-up from 0 over ``warmup_steps``, then cosine to 10% of
    the peak at ``schedule_steps``."""
    base, w, total = (job["learning_rate"], job["warmup_steps"],
                      job["schedule_steps"])
    if step < w:
        return base * step / max(w, 1)
    prog = min(max((step - w) / max(total - w, 1), 0.0), 1.0)
    return base * (0.1 + 0.9 * 0.5 * (1 + np.cos(np.pi * prog)))


def reference_placement(c: dict, devices) -> Optional[object]:
    """Where the reference keeps its state on several chips: every leaf
    over a 1-D mesh of ``devices``, split along its largest axis that
    their number divides (the last of equals), whole on each where none
    does.  None on one chip: the default device, as ever."""
    if not devices or len(devices) < 2:
        return None
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    n = len(devices)
    mesh = Mesh(np.asarray(devices), ("ref",))

    def one(x):
        axes = [i for i, k in enumerate(x.shape) if k % n == 0]
        if not axes:
            return NamedSharding(mesh, P())
        ax = max(axes, key=lambda i: (x.shape[i], i))
        return NamedSharding(mesh, P(*(["ref" if i == ax else None
                                        for i in range(x.ndim)])))
    return jax.tree.map(one, weights.shapes(c))


def reference_steps(c: dict, job: dict, params, batches, dtype=None,
                    quant: bool = False, placement=None) -> Dict:
    """The three steps of mixed-precision AdamW as the configuration
    states it: float32 master weights, the forward and backward at the
    master rounded to the weights' dtype (bfloat16), bias-corrected
    moments, decoupled weight decay, gradients clipped to a global norm,
    on the mean cross entropy, summed over blocks of rows.  ``params``
    lie as ``placement`` (:func:`reference_placement`) says, and so does
    every tree of state and gradients made from them."""
    import jax
    import jax.numpy as jnp
    from benchlib.spec import load_reference
    ref = load_reference(c)
    dtype = dtype or jnp.float32
    blk = job["check_rows"]

    def block_grad(w, tokens, targets, mask):
        def f(w):
            return ref.loss(c, w, {"tokens": tokens, "targets": targets,
                                   "mask": mask}, dtype=dtype, quant=quant)
        (s, n), g = jax.value_and_grad(f, has_aux=True)(w)
        return s, n, g

    if placement is None:
        block_grad = jax.jit(block_grad)
    else:
        whole = jax.sharding.NamedSharding(
            jax.tree.leaves(placement)[0].mesh, jax.sharding.PartitionSpec())
        block_grad = jax.jit(block_grad,
                             out_shardings=(whole, whole, placement))

    w = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    w0 = w
    losses, grad0 = [], None
    wdt = jnp.dtype(c["dtype"])
    for step, b in enumerate(batches):
        tot_s, tot_n, g = 0.0, 0.0, jax.tree.map(jnp.zeros_like, w)
        served = jax.tree.map(lambda x: x.astype(wdt).astype(jnp.float32), w)
        for r in range(0, b["tokens"].shape[0], blk):
            s, n, gb = block_grad(served, *(jnp.asarray(b[k][r:r + blk])
                                       for k in ("tokens", "targets",
                                                 "mask")))
            tot_s, tot_n = tot_s + s, tot_n + n
            g = jax.tree.map(jnp.add, g, gb)
        losses.append(float(tot_s / tot_n))
        g = jax.tree.map(lambda x: x / tot_n, g)
        gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
        g = jax.tree.map(
            lambda x: x * jnp.minimum(1.0, job["grad_clip"]
                                      / jnp.maximum(gn, 1e-9)), g)
        if step == 0:
            grad0 = {k: float(x) for k, x in _leaf_norms(g).items()}
            grad_vec = _leaves(g)
        lr = _schedule(step, job)
        b1, b2, eps, wd = job["b1"], job["b2"], job["eps"], job["weight_decay"]
        n_step = step + 1
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
        c1, c2 = 1 - b1 ** n_step, 1 - b2 ** n_step
        w = jax.tree.map(
            lambda p, a, s2: p - lr * ((a / c1) / (jnp.sqrt(s2 / c2) + eps)
                                       + wd * p), w, m, v)
    change = {k: float(x) for k, x in _leaf_norms(
        jax.tree.map(jnp.subtract, w, w0)).items()}
    return {"loss": losses, "grad": grad0, "change": change,
            "grad_vec": grad_vec}


def compare(prog: Dict, ref: Dict, floor: float) -> Dict[str, float]:
    """Worst-leaf gaps between the program's norms and the reference's,
    each over the larger of that leaf's reference norm and the median
    leaf's.  Leaves whose reference gradient is under ``floor`` times the
    median leaf's move by round-off alone and are left out of the change.
    ``grad_err`` is the one number not of norms: the norm of each leaf's
    first-gradient difference, element by element, on the same scale;
    the norms and the losses average the rounding of one precision and
    of the next below alike, the elements do not."""
    g_ref = ref["grad"]
    med_g = float(np.median(list(g_ref.values())))
    live = [k for k in g_ref if g_ref[k] >= floor * med_g]
    med_c = float(np.median([ref["change"][k] for k in live]))

    def worst(p, r, keys, med):
        return max(abs(p[k] - r[k]) / max(r[k], med) for k in keys)

    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(prog["loss"], ref["loss"])),
        "grad_gap": worst(prog["grad"], g_ref, list(g_ref), med_g),
        "change_gap": worst(prog["change"], ref["change"], live, med_c),
        "grad_err": max(
            float(np.linalg.norm(prog["grad_vec"][k] - ref["grad_vec"][k]))
            / max(g_ref[k], med_g) for k in g_ref),
    }


def check_numbers(t: Train, prog: Dict) -> Dict[str, float]:
    """Free the program's state, then follow the first three steps with
    the reference on the same weights and rows."""
    import jax
    t.params = t.opt = None
    gc.collect()
    where = reference_placement(t.c, t.devices)
    params = weights.make_params(t.c, t.seed, out_shardings=where)
    ref = reference_steps(t.c, t.job, params, t.batches, placement=where)
    del params
    jax.block_until_ready(0)
    print(f"losses: program {prog['loss']} reference {ref['loss']}",
          file=sys.stderr)
    return compare(prog, ref, t.job["dead_leaf_floor"])


def fault_numbers(c: dict, job: dict, seed: int, batches,
                  devices=None) -> Dict:
    """What the control and the faults a step can have read against the
    reference, each planted in the reference put in the program's place:
    the control (the reference with int8 matmuls, weights per output
    channel and activations per row, at bfloat16), a step that leaves out half its batch (the reference on the
    first half of each batch's rows, the mean over them; on two data
    replicas this is also what the first reads when the exchange of
    gradients between them is left out), and a step that returns its
    state unchanged (the optimizer's state stays zero, so the gradient
    read back and the change are zero, and every step's loss is taken at
    the first weights)."""
    import jax.numpy as jnp
    where = reference_placement(c, devices)
    params = weights.make_params(c, seed, out_shardings=where)
    ref = reference_steps(c, job, params, batches, placement=where)
    low = reference_steps(c, job, params, batches, dtype=jnp.bfloat16,
                          quant=True, placement=where)
    half = [{k: v[: v.shape[0] // 2] for k, v in b.items()}
            for b in batches]
    still = reference_steps(c, {**job, "learning_rate": 0.0}, params,
                            batches, placement=where)
    still["grad"] = {k: 0.0 for k in still["grad"]}
    still["grad_vec"] = {k: 0.0 * v for k, v in still["grad_vec"].items()}
    still["change"] = {k: 0.0 for k in still["change"]}
    floor = job["dead_leaf_floor"]
    return {"control": compare(low, ref, floor),
            "half_batch": compare(reference_steps(c, job, params, half,
                                                  placement=where),
                                  ref, floor),
            "state_unchanged": compare(still, ref, floor)}
