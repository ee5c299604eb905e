"""End-to-end training driver with the full production loop:

  data pipeline -> jitted train step (dense phase) -> SPION capture between
  epochs -> Frobenius transition -> pattern generation -> sparse phase ->
  checkpoints (atomic, async, keep-K) -> crash-restart supervisor ->
  straggler monitor.

CPU-runnable at reduced scale (examples/ wire it up); identical code paths
lower onto the production meshes (launch/dryrun.py proves compile).

Multi-host (DESIGN.md §12): launch one process per host with the SPION_*
env vars (or --coordinator/--num-processes/--process-id), and the same loop
becomes a fleet: `repro.distributed.runtime` joins jax.distributed, the mesh
gains a process-spanning 'pod' axis (make_distributed_mesh), flood-fill runs
single-controller on process 0 with the plan broadcast + digest-checked,
checkpoints are process-0-written/all-read with a commit barrier, and a
SIGTERM on any host triggers a fleet-wide same-step save and clean exit
(elastic resume onto a different process count re-shards from the
mesh-agnostic checkpoint and rebuilds the execs from the restored plan).

Self-healing (DESIGN.md §13): a DivergenceSentinel checks every step's loss
for NaN/inf and EWMA spikes; the flag rides the same per-step `any_flags`
OR as preemption, so the whole fleet rolls back at the SAME step to the
last *good* (pinned) checkpoint, skips the offending data window, and
hard-fails after `max_rollbacks` consecutive rollbacks. Run unattended
under `python -m repro.launch.supervise`, which scans the per-process
heartbeat files (JSON {ts, step, phase, ...}) and respawns the fleet when
a worker dies or its step counter freezes.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core.spion import SpionController, SpionState
from repro.data.synthetic import lm_batch_iterator
from repro.distributed import runtime
from repro.distributed.chaos import ChaosMonkey
from repro.distributed.fault import (DivergenceSentinel, Heartbeat,
                                     StepSupervisor, StragglerMonitor)
from repro.distributed.sharding import mesh_context, param_shardings
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_distributed_mesh
from repro.launch.steps import batch_pspecs, make_train_step
from repro.models.registry import build
from repro.optim import adamw_init


def masters_of(params):
    return jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32) if x.ndim >= 2 else x, params)


class Trainer:
    def __init__(self, cfg, *, seq_len, batch, lr=3e-4, total_steps=1000,
                 ckpt_dir=None, mesh=None, seed=0, steps_per_epoch=50,
                 data_iter=None, data_fn=None, capture_batches=1,
                 sparse_kernel=None, chaos=None, heartbeat_interval=5.0,
                 sentinel=None, max_rollbacks=3, step_callback=None):
        self.cfg = cfg
        self.bundle = build(cfg)
        self.mesh = mesh
        self.seq_len = seq_len
        self.steps_per_epoch = steps_per_epoch
        self.spion_ctl = SpionController(cfg.spion, causal=cfg.causal, seq_len=seq_len)
        self.spion_state = SpionState()
        self.monitor = StragglerMonitor()
        self.ckpt = CheckpointManager(ckpt_dir, keep=3) if ckpt_dir else None
        self.step = 0
        # `data_fn(step) -> host batch` is the fault-tolerant data contract:
        # step-indexed, so a resume replays the EXACT batch sequence the
        # uninterrupted run would have seen (a bare iterator restarts from
        # its beginning after a crash — fine for smoke runs, wrong for
        # step-exact recovery). In multi-process runs data_fn must return
        # the same *global* batch on every process; the 'pod'/'data' slice
        # each device keeps is carved off when the batch goes global.
        self.data_fn = data_fn
        if data_fn is None:
            rng = np.random.default_rng(seed)
            self.data = data_iter if data_iter is not None else lm_batch_iterator(
                rng, batch=batch, seq_len=seq_len + 1, vocab=cfg.vocab_size)
        # fault machinery: chaos is env-armed by default (inert when the
        # launcher sets no SPION_CHAOS_* vars); preemption flag set by the
        # SIGTERM handler (install_preemption_handler) and OR-reduced
        # across processes at each step boundary so the save runs in
        # lockstep
        self.chaos = chaos if chaos is not None else ChaosMonkey.from_env()
        self._preempted = False
        self.preempted = False          # observable: loop exited via preemption
        # divergence sentinel (DESIGN.md §13): default-on loss health check;
        # pass sentinel=False to disable. The local flag is OR-reduced
        # fleet-wide each step alongside preemption (one collective for both)
        # so every process rolls back at the SAME step.
        self.sentinel = DivergenceSentinel() if sentinel is None else (sentinel or None)
        self.max_rollbacks = max_rollbacks
        self.step_callback = step_callback
        self.data_offset = 0            # data windows skipped by rollbacks
        self.good_step = None           # last checkpoint known loss-healthy
        self.rollback_count = 0         # observable: total rollbacks performed
        self.loss_history = {}          # step -> loss; replays overwrite (stitched)
        self.events = []                # structured fault events (also printed)
        self._diverged_pending = False
        self._diverge_step = None
        self._last_diverge_step = None
        self._rollback_streak = 0       # consecutive rollbacks w/o healthy progress
        self._straggler_steps = 0
        self.heartbeat = None
        if ckpt_dir:
            self.heartbeat = Heartbeat(
                os.path.join(ckpt_dir, f"hb_{runtime.process_index()}"),
                interval=heartbeat_interval)

        params = self.bundle.init(jax.random.key(seed))
        self.params = masters_of(params)
        self.opt = adamw_init(self.params)

        self._dense_step = jax.jit(make_train_step(
            cfg, spion=False, lr=lr, total_steps=total_steps), donate_argnums=(0, 1))
        # one jitted sparse step for the whole run: the step receives a
        # SparseAttentionExec whose static block/halo ride the pytree
        # aux_data, so a NEW plan (different halo after a phase transition
        # or a sparse-phase resume) retraces automatically — no caller-side
        # halo tracking or lazy step rebuilds (DESIGN.md §11)
        self._sparse_step = jax.jit(make_train_step(
            cfg, spion=True, lr=lr, total_steps=total_steps,
            sparse_kernel=sparse_kernel), donate_argnums=(0, 1))
        capture_fn = lambda p, b, f, blk: self.bundle.forward(
            p, b, capture={"filt": f, "block": blk})[1]["captured"]
        if mesh is not None and runtime.process_count() > 1:
            # the capture stats feed the HOST-side flood-fill: with the mesh
            # spanning processes the outputs must come back fully
            # replicated, or np.asarray on a partially-addressable global
            # array would throw on every process but 0
            self._capture = jax.jit(capture_fn, static_argnames=("blk",),
                                    out_shardings=NamedSharding(mesh, P()))
        else:
            self._capture = jax.jit(capture_fn, static_argnames=("blk",))
        self.supervisor = StepSupervisor(self._restore_latest)

    # -- multi-process plumbing ----------------------------------------------

    def install_preemption_handler(self):
        """SIGTERM -> finish the in-flight step, then save and exit cleanly
        (the fleet-wide OR in the loop makes every process save at the SAME
        step even when the signal lands on one host). Main thread only."""
        def _handler(signum, frame):
            self._preempted = True
        signal.signal(signal.SIGTERM, _handler)

    def _device_batch(self, batch):
        """Host batch -> device. Multi-process: every process holds the same
        global batch; build committed global arrays sharded over the
        'pod'/'data' axes so each device keeps only its slice."""
        if self.mesh is not None and runtime.process_count() > 1:
            return runtime.make_global(
                self.mesh, batch, batch_pspecs(self.cfg, batch, self.mesh))
        return {k: jnp.asarray(v) for k, v in batch.items()}

    def _next_batch(self):
        # `+ data_offset`: each divergence rollback advances the offset past
        # the poisoned window, so the replayed steps see FRESH data while
        # staying step-indexed (resume-exact) — DESIGN.md §13.
        b = self.data_fn(self.step + self.data_offset) if self.data_fn \
            else next(self.data)
        return self._device_batch(b)

    # -- checkpoint/restart --------------------------------------------------

    def _state_tree(self):
        return {"params": self.params, "opt": self.opt}

    def save(self):
        if not self.ckpt:
            return
        # plan tables go binary (extra_arrays) — the JSON extra keeps only
        # scalars, so a production-size SparsityPlan doesn't bloat meta.
        # In multi-process runs this is a collective (all-gather to host
        # on every process; process 0 writes) — every process calls it.
        # A step with a divergence flag pending is saved but NOT promoted to
        # good_step (its state is already poisoned); the rollback quarantines
        # it. `_diverged_pending` derives from the global-mean loss, so the
        # healthy/poisoned decision is identical on every process.
        healthy = not self._diverged_pending
        if healthy:
            # pin BEFORE the (async) write: _gc runs on the writer thread
            # and must already see the new good step as protected
            self.ckpt.pin(self.step)
        arrays = self.spion_state.table_arrays()
        self.ckpt.save(
            self.step, self._state_tree(),
            extra={"spion": self.spion_state.to_py(include_tables=False),
                   "step": self.step, "data_offset": self.data_offset},
            extra_arrays=None if arrays is None else
            {f"spion_{k}": v for k, v in arrays.items()})
        if healthy:
            if self.good_step is not None and self.good_step != self.step:
                self.ckpt.unpin(self.good_step)
            self.good_step = self.step
            if (self._last_diverge_step is not None
                    and self.step > self._last_diverge_step):
                self._rollback_streak = 0  # healthy progress past the spike

    def _restore_shardings(self):
        """Shardings for the state tree on the CURRENT mesh — the elastic
        half of recovery: the checkpoint is mesh-agnostic (fully gathered),
        and restore re-shards it for however many processes/devices this
        incarnation of the job has."""
        if self.mesh is None:
            return None
        psh = param_shardings(self.mesh, self.params)
        rep = NamedSharding(self.mesh, P())
        return {"params": psh,
                "opt": {"mu": psh, "nu": psh, "count": rep}}

    def _restore_latest(self, step=None):
        if not self.ckpt:
            return
        tree, got, extra = self.ckpt.restore(
            step=step, target=self._state_tree(),
            shardings=self._restore_shardings())
        if tree is not None:
            self.params, self.opt = tree["params"], tree["opt"]
            self.step = extra.get("step", got or 0)
            self.data_offset = int(extra.get("data_offset", 0))
            # whatever we restore from is by definition our rollback target
            # until a newer healthy save supersedes it — pin it so GC can't
            # age it out of the keep window while training runs past it
            self.good_step = self.step
            self.ckpt.pin(self.step)
            if extra.get("spion"):
                arrays = {k[len("spion_"):]: v
                          for k, v in extra.get("_arrays", {}).items()
                          if k.startswith("spion_")} or None
                self.spion_state = SpionState.from_py(extra["spion"], arrays)
                # every process read the checkpoint independently; a torn
                # read or mixed-up dir on one host must fail loudly, not
                # train through a different pattern (DESIGN.md §12)
                self.spion_ctl.verify_plan_sync(self.spion_state)

    def maybe_resume(self):
        if self.ckpt and self.ckpt.latest_step() is not None:
            self._restore_latest()
            return True
        return False

    # -- steps ----------------------------------------------------------------

    def _one_step(self, batch):
        ex = self.spion_ctl.attention_exec(self.spion_state)
        if ex is not None:
            self.params, self.opt, metrics = self._sparse_step(
                self.params, self.opt, batch, jnp.int32(self.step), ex)
        else:
            self.params, self.opt, metrics = self._dense_step(
                self.params, self.opt, batch, jnp.int32(self.step))
        self.step += 1
        return metrics

    def _epoch_boundary(self, batch):
        """SPION capture + transition check on a capture batch. Pattern
        generation inside observe_epoch is single-controller: process 0
        flood-fills, everyone receives the broadcast plan (core/spion.py)."""
        cap = self.spion_ctl.capture_kwargs(self.spion_state)
        if cap is None:
            self.spion_state.epoch += 1
            return
        pc = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.dtype(self.cfg.dtype)) if x.ndim >= 2 and
            x.dtype == jnp.float32 else x, self.params)
        pooled, frob = self._capture(pc, batch, cap["filt"], cap["block"])
        self.spion_state = self.spion_ctl.observe_epoch(
            self.spion_state, np.asarray(pooled), np.asarray(frob))

    def _poll_flags(self):
        """Fleet-wide (preempted, diverged) decision, same answer on every
        process at the same step. Both flags ride ONE allgather per step in
        multi-process runs (any_flags), and it runs on the main thread at
        the loop top — collectives must never interleave with training-step
        collectives, and every process must reach this point at the same
        step for the OR to be well-defined (DESIGN.md §13)."""
        if runtime.process_count() > 1:
            return tuple(runtime.any_flags(
                [self._preempted, self._diverged_pending]))
        return self._preempted, self._diverged_pending

    def _emit(self, kind: str, **fields):
        """Structured fault event: appended to self.events on every process,
        printed (one JSON line, `SPION_EVENT {...}`) by the coordinator only
        so a supervisor/launcher tailing stdout sees each event once."""
        ev = {"event": kind, "step": self.step, "process": runtime.process_index()}
        ev.update(fields)
        self.events.append(ev)
        if runtime.is_coordinator():
            print("SPION_EVENT " + json.dumps(ev), flush=True)

    def _poison_params(self):
        """Chaos NaN injection: overwrite this process's addressable shards
        of every float param with NaN. Purely local (no jit, no collective
        — only the armed process runs it); the NEXT real step spreads the
        poison fleet-wide through the gradient psum, which is exactly the
        divergence propagation model the sentinel exists for."""
        def leaf(x):
            x = x if isinstance(x, jax.Array) else jnp.asarray(x)
            if not jnp.issubdtype(x.dtype, jnp.floating):
                return x
            shards = [jax.device_put(
                np.full(s.data.shape, np.nan, dtype=x.dtype), s.device)
                for s in x.addressable_shards]
            return jax.make_array_from_single_device_arrays(
                x.shape, x.sharding, shards)
        self.params = jax.tree_util.tree_map(leaf, self.params)

    def _rollback(self, log):
        """Coordinated divergence rollback (DESIGN.md §13): every process
        reaches here at the same step (the any_flags OR), agrees on the
        fleet-wide divergence step (max over local observations), quarantines
        checkpoints saved after the last good step, restores the pinned good
        checkpoint, and skips the poisoned data window so the replay sees
        fresh batches. Hard-fails after `max_rollbacks` consecutive
        rollbacks with no healthy checkpoint in between — at that point the
        divergence is not data-borne and a human needs to look."""
        t0 = time.time()
        local_d = -1 if self._diverge_step is None else self._diverge_step
        d = runtime.max_value(local_d) if runtime.process_count() > 1 else local_d
        self.rollback_count += 1
        self._rollback_streak += 1
        if self._rollback_streak > self.max_rollbacks:
            raise RuntimeError(
                f"loss diverged through {self.max_rollbacks} consecutive "
                f"rollbacks (last at step {d}): not recoverable by replay")
        g = self.good_step
        if self.ckpt is None or g is None:
            raise RuntimeError(
                f"loss diverged at step {d} but there is no good checkpoint "
                "to roll back to (enable checkpointing / lower ckpt_every)")
        self.ckpt.quarantine_after(g)       # poisoned saves must never restore
        self._restore_latest(step=g)        # also restores data_offset as-of g
        skip = (d - g + 1) if d >= g else 1
        self.data_offset += skip
        self._diverged_pending = False
        self._diverge_step = None
        self._last_diverge_step = d
        if self.sentinel:
            self.sentinel.reset()           # don't inherit spike-adjacent EWMA
        self._emit("rollback", from_step=d, to_step=g, skip=skip,
                   data_offset=self.data_offset,
                   seconds=round(time.time() - t0, 3))
        log(f"divergence at step {d}: rolled back to step {g}, skipping "
            f"data window [{g}, {d}] (offset now {self.data_offset}, "
            f"streak {self._rollback_streak}/{self.max_rollbacks})")

    def train(self, num_steps, *, ckpt_every=100, log_every=10, log=print):
        log0 = log if runtime.is_coordinator() else (lambda *a, **k: None)
        if self.heartbeat:
            self.heartbeat.pulse()          # announce liveness immediately
            self.heartbeat.start_thread()   # keeps ts fresh even mid-step
        try:
            with mesh_context(self.mesh):
                return self._train_loop(num_steps, ckpt_every, log_every, log0)
        finally:
            if self.heartbeat:
                self.heartbeat.stop_thread()

    def _train_loop(self, num_steps, ckpt_every, log_every, log0):
        t_total = time.time()
        losses = []
        target = self.step + num_steps
        while self.step < target:
            if self.chaos:
                self.chaos.maybe_kill(self.step)
                self.chaos.maybe_hang(self.step)
            preempted, diverged = self._poll_flags()
            if diverged:
                self._rollback(log0)
                continue
            if preempted:
                self.preempted = True
                self.save()
                if self.ckpt:
                    self.ckpt.wait()
                log0(f"preempted: saved step {self.step}, exiting")
                return losses
            batch = self._next_batch()
            if self.chaos and self.chaos.poison_due(self.step):
                self._poison_params()
            t0 = time.time()
            metrics = self.supervisor.run(self._one_step, batch)
            dt = time.time() - t0
            loss = float(metrics["loss"])
            losses.append(loss)
            self.loss_history[self.step - 1] = loss  # replay overwrites: stitched
            if self.sentinel and self.sentinel.observe(loss):
                # local observation only; the fleet decision is the OR at
                # the top of the NEXT iteration, so every process rolls
                # back at the same step
                self._diverged_pending = True
                self._diverge_step = self.step - 1
                self._emit("divergence", loss=loss,
                           streak=self._rollback_streak)
            straggler = self.monitor.observe(dt)
            if straggler:
                self._straggler_steps += 1
                self._emit("straggler", dt=round(dt, 4),
                           total=self._straggler_steps)
            if self.heartbeat:
                self.heartbeat.beat(step=self.step,
                                    phase=self.spion_state.phase,
                                    extra={"stragglers": self._straggler_steps})
            if self.step_callback:
                self.step_callback(self.step - 1, loss)
            if self.step % log_every == 0:
                log0(f"step {self.step} loss {np.mean(losses[-log_every:]):.4f} "
                     f"phase {self.spion_state.phase} dt {dt*1e3:.0f}ms"
                     + (" [straggler]" if straggler else ""))
            if self.step % self.steps_per_epoch == 0 and not self._diverged_pending:
                # a poisoned epoch boundary would flood-fill NaN capture
                # stats; the imminent rollback replays the boundary from
                # healthy state anyway (same decision on every process:
                # the flag derives from the global-mean loss)
                self._epoch_boundary(batch)
            if ckpt_every and self.step % ckpt_every == 0:
                self.save()
        self.save()
        if self.ckpt:
            self.ckpt.wait()
        log0(f"done: {num_steps} steps in {time.time()-t_total:.1f}s, "
             f"final phase={self.spion_state.phase} density={self.spion_state.density}")
        return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="spion-lra")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--sparse-kernel", default=None,
                    choices=["auto", "jnp", "fused"],
                    help="sparse-phase attention impl (default: cfg.spion.kernel; "
                         "auto = fused Pallas kernel on TPU or where a mesh "
                         "dim shards it, jnp path elsewhere)")
    ap.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                    help="jax.distributed coordinator (or env SPION_COORDINATOR); "
                         "with --num-processes/--process-id this process joins "
                         "a multi-host job")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    args = ap.parse_args()
    distributed = runtime.initialize(args.coordinator, args.num_processes,
                                     args.process_id)
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    # every local chip joins the mesh: without one, a multi-chip host would
    # train on its first chip alone
    mesh = (make_distributed_mesh()
            if distributed or jax.device_count() > 1 else None)
    tr = Trainer(cfg, seq_len=args.seq_len, batch=args.batch,
                 ckpt_dir=args.ckpt_dir, mesh=mesh,
                 sparse_kernel=args.sparse_kernel)
    tr.install_preemption_handler()
    tr.maybe_resume()
    tr.train(args.steps)


if __name__ == "__main__":
    main()
