#!/usr/bin/env python3
"""Chip smoke run: SPION training and serving on a TPU, through the entry
points a user calls (launch/train.Trainer, launch/serve.ServeEngine,
core/attention_exec.SparseAttentionExec and the fused Mosaic kernels).

    python chip_smoke.py             # one chip: phases `train` then `serve`
    python chip_smoke.py --chips 4   # four chips: the sharded sparse train
                                     # step on two meshes, nothing else

`train` trains the paper's own model (spion-lra, as registered) on
generated ListOps at L=2048 through the dense phase, the capture, the
flood fill and into the sparse phase; `serve` answers requests on a decoder
at qwen2-7b widths with its depth cut to fit one chip. Earlier lines are
facts from this run (compile and step seconds, peak bytes, errors against
the references), not benchmark metrics. The last line is one JSON object,
{"ok": true, "device": {...}}; any failed check exits non-zero before it.
Without a TPU the script refuses to run.

The phases are plain functions of a config and sizes, so the tests run
them on CPU at reduced size (tests/test_chip_smoke.py).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.attention_exec import SparseAttentionExec  # noqa: E402
from repro.data.listops import make_listops_batch  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import Request, ServeEngine  # noqa: E402
from repro.launch.steps import (causal_band_tables,  # noqa: E402
                                make_prefill_step)
from repro.launch.train import Trainer  # noqa: E402
from repro.models.registry import build  # noqa: E402

# fused kernel vs the float32 jnp reference, as max|err| / max|ref|: the
# kernel's MXU passes round their operands to bfloat16 (relative 2^-8)
ATTN_TOL = 2e-2
# sparse vs dense prefill logits under a covering plan, as
# ||sparse - dense|| / ||dense|| over each row block of the prompt (the
# worst block): both run the bf16 model, and they differ only in how
# attention rounds (f32 flash kernel vs bf16 einsum). Per block, so that a
# block the kernel skips is not diluted by the rest of a long prompt.
LOGITS_TOL = 5e-2
# one-chip vs mesh sparse step: loss, gradient norm and Adam's first moment
# (a scaled gradient, as relative L2 over all weights); the bf16 model's
# reductions reorder across shards
MESH_LOSS_TOL = 1e-3
MESH_GRAD_TOL = 2e-2


def fact(**kw):
    """One line of facts from this run (not a benchmark metric)."""
    print("chip fact: " + json.dumps(kw), flush=True)


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def listops_fn(batch, seq_len, seed):
    """Step-indexed ListOps batches (the Trainer's data_fn contract)."""
    def data_fn(step):
        xs, _ = make_listops_batch(np.random.default_rng(seed + step), batch,
                                   seq_len + 1)
        return {"tokens": xs[:, :-1], "labels": xs[:, 1:]}
    return data_fn


def rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def tree_rel_l2(a, b):
    """||a - b|| / ||b|| over every leaf of two pytrees."""
    num = den = 0.0
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        num += float(np.sum((x - y) ** 2))
        den += float(np.sum(y ** 2))
    return float(np.sqrt(num / max(den, 1e-300)))


def check(ok, what):
    if not ok:
        raise SystemExit(f"chip_smoke: check failed: {what}")


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def attention_agreement(cfg, ex, *, batch, seed):
    """Fused kernel vs jnp BCSR path on the trained plan, every layer:
    max relative error of the attention output and of dq, dk, dv."""
    S = ex.coverage
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (batch, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (batch, S, KV, hd), jnp.float32)
    v = jax.random.normal(ks[2], (batch, S, KV, hd), jnp.float32)
    ct = jax.random.normal(ks[3], (batch, S, H, hd), jnp.float32)

    def run(kernel):
        """Layer index -> (out, dq, dk, dv) through `kernel`'s own exec."""
        pinned = SparseAttentionExec(ex.tables, block=ex.block, halo=ex.halo,
                                     kernel=kernel)
        tables = pinned.scan_tables()

        @jax.jit
        def f(q, k, v, tabs):
            o, vjp = jax.vjp(lambda *a: pinned.attend(cfg, *a, tabs), q, k, v)
            return (o,) + vjp(ct)
        return lambda layer: f(q, k, v, {n: t[layer]
                                         for n, t in tables.items()})

    fused, ref = run("fused"), run("jnp")
    worst = [0.0] * 4
    for layer in range(ex.tables["col_idx"].shape[0]):
        got = fused(layer)
        with jax.default_matmul_precision("highest"):
            want = ref(layer)
        worst = [max(w, rel_err(g, r)) for w, g, r in zip(worst, got, want)]
    return dict(zip(("out", "dq", "dk", "dv"), worst))


def train_phase(cfg, *, seq_len, batch, steps_per_epoch, sparse_steps,
                check_batch, seed=0):
    """Dense steps, capture, flood fill, then sparse steps through Trainer.
    Returns facts; raises on a failed check."""
    cfg = cfg.replace(spion=dataclasses.replace(
        cfg.spion, min_dense_epochs=1, max_dense_epochs=2))
    fact(phase="train", arch=cfg.name, seq_len=seq_len, batch=batch,
         layers=cfg.num_layers, d_model=cfg.d_model, heads=cfg.num_heads,
         block=cfg.spion.block_size)
    data_fn = listops_fn(batch, seq_len, seed)
    stamps = []
    tr = Trainer(cfg, seq_len=seq_len, batch=batch, seed=seed,
                 steps_per_epoch=steps_per_epoch, data_fn=data_fn,
                 step_callback=lambda s, loss: stamps.append(
                     (time.perf_counter(), loss)))
    quiet = lambda *a, **k: None  # noqa: E731
    t0 = time.perf_counter()
    losses = tr.train(2 * steps_per_epoch, log=quiet)
    t_dense = time.perf_counter() - t0
    check(tr.spion_state.phase == "sparse",
          f"phase after {2 * steps_per_epoch} steps is "
          f"{tr.spion_state.phase!r}, not 'sparse'")
    n_dense = len(stamps)
    dense_dt = np.diff([t0] + [t for t, _ in stamps])
    t1 = time.perf_counter()
    losses += tr.train(sparse_steps, log=quiet)
    sparse_dt = np.diff([t1] + [t for t, _ in stamps[n_dense:]])
    check(len(losses) == 2 * steps_per_epoch + sparse_steps,
          f"took {len(losses)} steps")
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss in {losses}")

    ex = tr.spion_ctl.attention_exec(tr.spion_state)
    t2 = time.perf_counter()
    b = data_fn(0)
    host_batch_s = time.perf_counter() - t2
    b = {k: jnp.asarray(v) for k, v in b.items()}
    lowered = tr._sparse_step.lower(tr.params, tr.opt, b,
                                    jnp.int32(tr.step), ex).as_text()
    err = attention_agreement(cfg, ex, batch=check_batch, seed=seed + 1)
    check(max(err.values()) <= ATTN_TOL,
          f"fused vs jnp attention error {err} > {ATTN_TOL}")
    facts = dict(phase="train", dense_steps=n_dense,
                 sparse_steps=len(losses) - n_dense,
                 dense_phase_s=t_dense,
                 dense_step_s=float(np.median(dense_dt[1:])),
                 first_sparse_step_s=float(sparse_dt[0]),
                 sparse_step_s=float(np.median(sparse_dt[1:])),
                 host_batch_s=host_batch_s,
                 density=tr.spion_state.density,
                 plan_width=int(ex.tables["col_idx"].shape[-1]),
                 loss_first=losses[0], loss_last=losses[-1],
                 fused_vs_jnp_rel_err=err, tolerance=ATTN_TOL,
                 tpu_custom_call="tpu_custom_call" in lowered,
                 peak_bytes_in_use=peak_bytes())
    fact(**facts)
    return facts


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _requests(rng, cfg, lens, max_new, prefix=None):
    out = []
    for i, n in enumerate(lens):
        p = rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
        if prefix is not None:
            p = np.concatenate([prefix, p])
        out.append(Request(rid=i, prompt=p, max_new=max_new))
    return out


def _serve(label, eng, reqs):
    t0 = time.perf_counter()
    eng.run(reqs)
    dt = time.perf_counter() - t0
    check(all(r.done and len(r.out) == r.max_new for r in reqs),
          f"{label}: not every request was answered in full")
    f = dict(phase="serve", engine=label, requests=len(reqs),
             prompt_tokens=sum(len(r.prompt) for r in reqs),
             new_tokens=sum(len(r.out) for r in reqs),
             seconds_incl_compile=dt, peak_bytes_in_use=peak_bytes())
    fact(**f)
    return f


def prefill_agreement(cfg, params, tables, prompt):
    """Sparse prefill under the per-layer `tables` vs dense prefill of one
    prompt: relative L2 of the logits over the whole prompt and over its
    worst row block, and whether the lowered sparse prefill holds a Mosaic
    kernel."""
    block = cfg.spion.block_size
    P = len(prompt)
    toks = np.zeros((1, -(-P // block) * block), np.int32)
    toks[0, :P] = prompt
    batch = {"tokens": jnp.asarray(toks)}
    ex = SparseAttentionExec(tables, block=block, phase="prefill")
    step = jax.jit(make_prefill_step(cfg, spion=True, with_cache=True))
    dense = np.asarray(step(params, batch, None)[0][0, :P], np.float32)
    sparse = np.asarray(step(params, batch, ex)[0][0, :P], np.float32)

    def rel(a, b):
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))
    return dict(rel_l2=rel(sparse, dense),
                worst_block_rel_l2=max(rel(sparse[i:i + block],
                                           dense[i:i + block])
                                       for i in range(0, P, block)),
                tpu_custom_call="tpu_custom_call" in step.lower(
                    params, batch, ex).as_text())


def serve_phase(cfg, *, slots, max_len, prompt_lens, prefix_len,
                suffix_lens, max_new, prefill_bucket, seed=0):
    """Dense, sparse and shared-prefix serving through ServeEngine, plus
    the sparse-vs-dense prefill logits check. Returns facts."""
    block = cfg.spion.block_size
    fact(phase="serve", arch=cfg.name, layers=cfg.num_layers,
         d_model=cfg.d_model, heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
         head_dim=cfg.resolved_head_dim, d_ff=cfg.d_ff,
         vocab=cfg.vocab_size, block=block, slots=slots, max_len=max_len)
    params = jax.jit(build(cfg).init)(jax.random.key(seed))
    rng = np.random.default_rng(seed)
    plan = dict(causal_band_tables(cfg.num_layers, max_len // block),
                block=block)
    kw = dict(slots=slots, max_len=max_len, prefill_bucket=prefill_bucket)
    facts = {"engines": [
        _serve("dense", ServeEngine(cfg, params, **kw),
               _requests(rng, cfg, prompt_lens, max_new)),
        _serve("sparse", ServeEngine(cfg, params, spion=plan, **kw),
               _requests(rng, cfg, prompt_lens, max_new))]}
    prefix = rng.integers(0, cfg.vocab_size, prefix_len).astype(np.int32)
    eng = ServeEngine(cfg, params, **kw)
    facts["engines"].append(_serve(
        "shared_prefix", eng,
        _requests(rng, cfg, suffix_lens, max_new, prefix=prefix)))
    st = eng.prefix_stats
    check(st["hits"] > 0, f"shared prefix: no page hits ({st})")
    fact(phase="serve", engine="shared_prefix", prefix_hits=st["hits"],
         prefix_lookups=st["lookups"],
         prefix_tokens_reused=st["prefix_tokens_reused"],
         fused_prefills=st["prefill_fused"])
    del eng

    # sparse prefill under a covering plan must reproduce dense logits
    P = int(max(prompt_lens))
    toks = rng.integers(0, cfg.vocab_size, P).astype(np.int32)
    nrb = -(-P // block)
    agree = prefill_agreement(cfg, params, {k: v[:, :nrb] for k, v in
                                            plan.items() if k != "block"},
                              toks)
    check(np.isfinite(agree["rel_l2"]), "non-finite sparse prefill logits")
    check(agree["worst_block_rel_l2"] <= LOGITS_TOL,
          f"sparse vs dense prefill logits error {agree} > {LOGITS_TOL}")
    facts.update(prefill_len=P, sparse_vs_dense_logits_rel_l2=agree["rel_l2"],
                 worst_block_rel_l2=agree["worst_block_rel_l2"],
                 tolerance=LOGITS_TOL,
                 tpu_custom_call=agree["tpu_custom_call"])
    fact(phase="serve", prefill_len=P,
         sparse_vs_dense_logits_rel_l2=agree["rel_l2"],
         worst_block_rel_l2=agree["worst_block_rel_l2"], tolerance=LOGITS_TOL,
         tpu_custom_call=agree["tpu_custom_call"],
         peak_bytes_in_use=peak_bytes())
    return facts


# ---------------------------------------------------------------------------
# four chips: the shard_map'd sparse train step
# ---------------------------------------------------------------------------

def mesh_phase(cfg, meshes, *, seq_len, batch, seed=0):
    """The sparse train step on each mesh vs the same step meshless on one
    chip, same batch and parameters. Returns facts per mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed.sharding import mesh_context, param_shardings
    from repro.launch.steps import (batch_pspecs, make_train_step,
                                    spion_dryrun_tables)
    from repro.launch.train import masters_of
    from repro.optim import adamw_init

    # a near-diagonal stand-in plan, so the (seq, data) mesh can shard the
    # sequence with a bounded halo
    tabs = spion_dryrun_tables(cfg, seq_len, max_extent=2)
    ex = SparseAttentionExec({k: v for k, v in tabs.items()
                              if hasattr(v, "shape")},
                             block=cfg.spion.block_size, halo=tabs["halo"])
    params = masters_of(jax.jit(build(cfg).init)(jax.random.key(seed)))
    opt = adamw_init(params)
    data = listops_fn(batch, seq_len, seed)(0)
    step_no = jnp.int32(199)          # end of warm-up: the full learning rate
    ref_p, ref_o, ref_m = jax.jit(make_train_step(cfg, spion=True))(
        params, opt, data, step_no, ex)
    out = []
    for name, mesh in meshes:
        # a step jitted per mesh: the mesh is read while tracing
        step = jax.jit(make_train_step(cfg, spion=True))
        with mesh_context(mesh):
            psh = param_shardings(mesh, params)
            p = jax.device_put(params, psh)
            o = jax.device_put(opt, {"mu": psh, "nu": psh,
                                     "count": NamedSharding(mesh, P())})
            bsp = batch_pspecs(cfg, data, mesh)
            b = {k: jax.device_put(v, NamedSharding(mesh, bsp[k]))
                 for k, v in data.items()}
            lowered = step.lower(p, o, b, step_no, ex)
            text = lowered.compile().as_text()
            got_p, got_o, got_m = step(p, o, b, step_no, ex)
        leaves = jax.tree_util.tree_leaves(got_p)
        f = dict(phase="mesh", mesh=name, shape=dict(mesh.shape),
                 devices=len(mesh.devices.flat),
                 param_devices=min(len(x.sharding.device_set) for x in leaves),
                 params_split=sum(x.sharding.shard_shape(x.shape) != x.shape
                                  for x in leaves),
                 tokens_shard_shape=list(
                     b["tokens"].sharding.shard_shape(b["tokens"].shape)),
                 shard_map="sdy.manual_computation" in lowered.as_text(),
                 tpu_custom_call="tpu_custom_call" in text,
                 loss=float(got_m["loss"]), loss_ref=float(ref_m["loss"]))
        f["loss_rel_err"] = abs(f["loss"] - f["loss_ref"]) / abs(f["loss_ref"])
        f["gnorm_rel_err"] = abs(float(got_m["gnorm"]) - float(ref_m["gnorm"])
                                 ) / float(ref_m["gnorm"])
        f["mu_rel_l2"] = tree_rel_l2(got_o["mu"], ref_o["mu"])
        # Adam's first step moves a weight by lr * sign(g) (+ weight decay):
        # a near-zero gradient that flips sign differs by 2 * lr
        lr = float(ref_m["lr"])
        f["param_max_abs_err"] = max(float(np.max(np.abs(
            np.asarray(g) - np.asarray(r)))) for g, r in zip(
            jax.tree_util.tree_leaves(got_p), jax.tree_util.tree_leaves(ref_p)))
        fact(**f)
        check(f["devices"] == 4 and f["param_devices"] == 4 and
              f["tokens_shard_shape"] != list(b["tokens"].shape),
              f"{name}: parameters or batch do not span the mesh")
        check(f["shard_map"], f"{name}: shard_map missing from the step")
        check(f["loss_rel_err"] <= MESH_LOSS_TOL, f"{name}: loss differs")
        check(f["gnorm_rel_err"] <= MESH_GRAD_TOL and
              f["mu_rel_l2"] <= MESH_GRAD_TOL, f"{name}: gradients differ")
        check(f["param_max_abs_err"] <= 2.5 * lr, f"{name}: params differ")
        out.append(f)
    return out


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} chips",
              file=sys.stderr)
        return 2
    fact(compile_cache=enable_compile_cache(), jax=jax.__version__,
         device_kind=devs[0].device_kind, chips=len(devs))
    t0 = time.perf_counter()
    if args.chips == 4:
        from repro.launch.mesh import make_mesh, make_seq_mesh
        meshes = [("data2_model2", make_mesh((2, 2), ("data", "model"))),
                  ("seq2_data2", make_seq_mesh(2, 2))]
        for f in mesh_phase(get_config("spion-lra"), meshes, seq_len=2048,
                            batch=32):
            check(f["tpu_custom_call"], f"{f['mesh']}: no Mosaic kernel")
    else:
        lra = get_config("spion-lra")
        f = train_phase(lra, seq_len=2048, batch=128, steps_per_epoch=3,
                        sparse_steps=4, check_batch=2)
        check(f["tpu_custom_call"], "sparse train step has no Mosaic kernel")
        # qwen2-7b at its published widths; 16 of 28 layers (9.0 GiB of bf16
        # weights) leave room on a 16 GiB chip for the cache and prefill
        qwen = get_config("qwen2-7b").replace(num_layers=16)
        lens = np.random.default_rng(7).integers(300, 2001, 8)
        f = serve_phase(qwen, slots=4, max_len=2176, prompt_lens=lens,
                        prefix_len=1024, suffix_lens=[8, 20, 33, 47, 60, 12,
                                                      25, 40],
                        max_new=16, prefill_bucket=1024)
        check(f["tpu_custom_call"], "sparse prefill has no Mosaic kernel")
    fact(total_s=time.perf_counter() - t0, peak_bytes_in_use=peak_bytes())
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
