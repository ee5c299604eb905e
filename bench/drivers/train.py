"""Training cells (traffic kind `train`): `Trainer.train` in the dense or
the sparse phase, on one chip or on the mesh the traffic names.

Set-up makes the weights from the seed and hands them to one `Trainer`.
In a sparse cell the program's own capture and flood fill first choose
the plan, with the dense warm-up cut to nothing, on weights and a batch
drawn from the mix's plan seed: every run seed trains under the same
plan, and so does the same work. The same Trainer then takes its first
three steps through `Trainer.train`, the call the window makes, on
batches whose rows all differ; those three steps are what the reference
checks, and they also compile and warm every program the window uses.
The window repeats `Trainer.train(1)` until --seconds have passed; a pool
of batches of the traffic's task made at set-up feeds it, so the window
measures the step and not Python data generation.

A traffic with a `mesh` ({"seq": 2, "data": 2}, its product the cell's
chips) trains on that mesh: the Trainer gets the mesh, and the weights,
optimizer state and batch pool are placed as the program lays them out
(`Trainer._restore_shardings`, `batch_pspecs`). No listed cell names a
mesh yet: this path has run on virtual CPU devices only, never on a
chip. The reference runs on one device.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, spec, weights

CHECK_STEPS = 3
# what calibrate.py reads beside the program: (name, the reference's
# precision, a divisor of the batch it keeps or 0 for all of it)
CONTROLS = (("control_fp8", "fp8", 0), ("control_int8", "int8", 0),
            ("fault_half_batch", "f32", 2))


def quiet(*a, **k):
    pass


def tree_norms(tree):
    """Leaf path -> float64 L2 norm, on the host."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {weights.leaf_name(p): float(np.linalg.norm(
        np.asarray(x, np.float64).ravel())) for p, x in flat}


def leaf_gap(prog, ref, keep):
    """Worst leaf of |‖prog‖ - ‖ref‖| / max(‖ref‖, median ‖ref‖) over the
    leaves in `keep`."""
    a, b = tree_norms(prog), tree_norms(ref)
    med = float(np.median([b[k] for k in keep]))
    return max(abs(a[k] - b[k]) / max(b[k], med) for k in keep)


def moved_leaves(ref_grad):
    """Leaves whose reference gradient is not nought to rounding: at least
    a thousandth of the median leaf's."""
    n = tree_norms(ref_grad)
    med = float(np.median(list(n.values())))
    return sorted(k for k, v in n.items() if v >= 1e-3 * med)


def compare(prog, ref):
    """The numbers the check reads; the cell's limits name those it
    compares. `prog`/`ref` each hold `losses` and `gnorms` (per step: the
    gradient's global norm before the clip), `grad1` (the first step's
    gradient as the optimizer gets it, after the clip) and `delta` (the
    parameters' change over the steps)."""
    keep = moved_leaves(ref["grad1"])

    def rel(key):
        return max(abs(p - r) / abs(r) for p, r in zip(prog[key], ref[key]))
    return {"loss_rel_gap": rel("losses"), "gnorm_rel_gap": rel("gnorms"),
            "grad1_norm_gap": leaf_gap(prog["grad1"], ref["grad1"], keep),
            "delta_norm_gap": leaf_gap(prog["delta"], ref["delta"], keep)}


def norms_of(steps):
    """Per-step losses and per-leaf norms of a run's first steps."""
    return {"losses": list(steps["losses"]),
            "gnorms": list(steps["gnorms"]),
            "grad1": tree_norms(steps["grad1"]),
            "delta": tree_norms(steps["delta"])}


def delta(after, before):
    return jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        after, before)


def reference_run(run, precision, params0, batches, masks, totals, rows=None):
    """The reference's first steps; `rows` keeps only that many rows of
    each batch (the planted half-batch fault)."""
    opt = run.cell.traffic["optimizer"]
    if rows is not None:
        batches = [{k: v[:rows] for k, v in b.items()} for b in batches]
    chunk = min(run.cell.traffic["reference_chunk"], len(batches[0]["tokens"]))
    losses, gnorms, g1, p3 = run.family.train_steps(
        run.model, opt, precision, params0, batches, masks, totals,
        first_step=0, chunk=chunk)
    return {"losses": losses, "gnorms": gnorms, "grad1": jax.device_get(g1),
            "delta": delta(jax.device_get(p3), params0)}


def cell_mesh(traffic, chips):
    """The mesh the traffic names, over the cell's chips; None without one.
    Its axes' product has to be the cell's chip count."""
    shape = dict(traffic.get("mesh") or {})
    if not shape:
        return None
    if int(np.prod(list(shape.values()))) != chips:
        raise ValueError(f"mesh {shape} does not span the cell's {chips} "
                         f"chips")
    from repro.launch.mesh import make_seq_mesh
    return make_seq_mesh(**shape)


def place_batch(cfg, mesh, b):
    """A host batch as device arrays, split over the mesh as the program's
    step expects (`batch_pspecs`) where there is one."""
    if mesh is None:
        return {k: jnp.asarray(v) for k, v in b.items()}
    from jax.sharding import NamedSharding
    from repro.launch.steps import batch_pspecs
    pspecs = batch_pspecs(cfg, b, mesh)
    return {k: jax.device_put(v, NamedSharding(mesh, pspecs[k]))
            for k, v in b.items()}


def build(run, seed, cfg, t_process=0.0):
    """The set-up the window and the check share: batches, weights, one
    Trainer, the plan. Returns (trainer, host batches, host weights)."""
    from repro.distributed.sharding import mesh_context
    from repro.launch.train import Trainer, masters_of
    from repro.models.registry import build as build_model
    t = run.cell.traffic
    B, L = t["batch"], t["seq_len"]
    if t["pool"] < CHECK_STEPS:
        raise ValueError(f"a pool of {t['pool']} batches cannot give the "
                         f"{CHECK_STEPS} checked steps rows that all differ")
    mesh = cell_mesh(t, run.cell.chips)
    task = spec.task(t["task"])
    batches = task.batches(seed, t, count=t["pool"],
                           vocab_size=cfg.vocab_size)
    dev_batches = [place_batch(cfg, mesh, b) for b in batches]
    shapes = jax.eval_shape(lambda k: masters_of(build_model(cfg).init(k)),
                            jax.random.key(0))
    params = weights.make(shapes, seed)
    host_params = jax.device_get(params)
    if t["phase"] == "sparse":
        # the program's own three-phase path, its dense warm-up cut to
        # nothing: the first epoch boundary captures and flood-fills
        cfg = cfg.replace(spion=dataclasses.replace(
            cfg.spion, min_dense_epochs=1, max_dense_epochs=1))
    opt = t["optimizer"]
    harness.note("batches and weights made", t_process)
    tr = Trainer(cfg, seq_len=L, batch=B, seed=0, lr=opt["lr"],
                 total_steps=opt["total_steps"],
                 steps_per_epoch=t["steps_per_epoch"], mesh=mesh,
                 data_fn=lambda s: dev_batches[s % len(dev_batches)])
    # on a mesh, the state as the program lays it out there
    shardings = tr._restore_shardings()
    if shardings is not None:
        tr.opt = jax.device_put(tr.opt, shardings["opt"])
    place = (lambda p: p) if shardings is None else \
        (lambda p: jax.device_put(p, shardings["params"]))
    if t["phase"] == "sparse":
        # the plan from weights and a batch drawn from the mix's plan seed:
        # every run seed trains under the same plan, so does the same work
        tr.params = place(weights.make(shapes, t["plan_seed"]))
        plan = task.batches(t["plan_seed"], t, count=1,
                            vocab_size=cfg.vocab_size)[0]
        with mesh_context(mesh):
            tr._epoch_boundary(place_batch(cfg, mesh, plan))
        harness.note("plan captured and flood-filled", t_process)
        if tr.spion_state.phase != "sparse":
            raise RuntimeError("the capture did not move the trainer to the "
                               "sparse phase")
    tr.params = place(params)
    return tr, batches, host_params


def run(run, *, seed, seconds, trace, t_process, compiles, control=False):
    from bench.spec import program_config
    cfg = program_config(run.cell.config)
    t = run.cell.traffic
    B, L = t["batch"], t["seq_len"]
    tr, batches, params0 = build(run, seed, cfg, t_process)
    st = tr.spion_state
    tables = st.tables if t["phase"] == "sparse" else None
    run.block = cfg.spion.block_size
    harness.plan_line(run.cell, tables, run.block, st.density)
    if tables is not None:
        run.plan_nvalid = list(np.asarray(tables["nvalid"]))

    # the first steps, through the window's own call: checked, and warm-up;
    # the step's own metrics give each gradient's norm before the clip,
    # which the clip and Adam's normalised update otherwise hide
    losses, gnorms, one_step = [], [], tr._one_step

    def checked_step(batch):
        metrics = one_step(batch)
        gnorms.append(metrics["gnorm"])
        return metrics
    tr._one_step = checked_step
    tr.step_callback = lambda s, loss: losses.append(loss)
    tr.train(1, log=quiet)
    harness.note("first step taken", t_process)
    mu1 = jax.device_get(tr.opt["mu"])
    tr.train(CHECK_STEPS - 1, log=quiet)
    del tr._one_step, one_step, checked_step   # they hold the trainer
    b1 = t["optimizer"]["b1"]
    prog = {"losses": list(losses), "gnorms": [float(g) for g in gnorms],
            "grad1": jax.tree_util.tree_map(lambda m: m / (1 - b1), mu1),
            "delta": delta(jax.device_get(tr.params), params0)}
    run.record["setup_s"] = time.perf_counter() - t_process
    harness.note("set up and checked steps taken", t_process)

    # the window
    compiles.armed = True
    tracer = harness.Tracer(run.cell.name) if trace else None
    steps, t0 = 0, time.perf_counter()
    if tracer:
        tracer.start()
        for _ in range(t["trace_steps"]):
            with harness.span("bench.step", True):
                tr.train(1, log=quiet)
            steps += 1
        tracer.stop()
        steps, t0 = 0, time.perf_counter()   # rates from the untraced rest
    while True:
        tr.train(1, log=quiet)
        steps += 1
        dt = time.perf_counter() - t0
        if dt >= seconds:
            break
    compiles.armed = False
    run.attempted = steps
    run.failed = int(sum(not np.isfinite(x) for x in losses[CHECK_STEPS:]))
    run.e2e["train_tokens_per_s"] = steps * B * L / dt
    run.e2e["setup_s"] = run.record["setup_s"]
    run.record.update(steps=steps, window_s=dt, batch=B, seq_len=L,
                      window_compiles=compiles.count)
    run.memory_peak_bytes = harness.memory_peak(run.cell.chips)

    # the check: free the program's state, then the reference
    masks_b = harness.block_masks(tables) if tables is not None \
        else np.ones((cfg.num_layers, 1, 1), bool)
    del tr
    gc.collect()
    masks, totals = zip(*[run.family.visible(m, L, run.model["causal"])
                          for m in masks_b])
    run.record["check_inputs"] = (params0, batches[:CHECK_STEPS], masks,
                                  totals[0])
    harness.note("window over", t_process)
    ref = reference_run(run, "f32", *run.record["check_inputs"])
    nums = compare(prog, ref)
    harness.note("reference compared", t_process)
    run.record["readings"] = dict(nums)
    if control:
        run.record["norms"] = {"program": norms_of(prog),
                               "reference": norms_of(ref)}
        for name, precision, rows in CONTROLS:
            out = reference_run(run, precision, *run.record["check_inputs"],
                                rows=B // rows if rows else None)
            run.record["readings"].update(
                {f"{name}.{k}": v for k, v in compare(out, ref).items()})
            run.record["norms"][name] = norms_of(out)
    lim = run.cell.limits["limits"]
    run.checks = [(k, nums[k], lim[k]) for k in sorted(lim)]
    run.checks.append(("window_compiles", float(compiles.count), 0.0))
    if tracer:
        run.trace = tracer.read()
