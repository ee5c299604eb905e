"""Serving cells: `ServeEngine` under an open loop of requests.

Set-up lets the program's own capture and flood fill choose the plan on
weights and a prompt of the cache's full length drawn from the mix's plan
seed (so every run seed serves under the same plan), makes the weights
from the run seed, builds one engine with that plan, and warms every
prefill length the mix can produce plus the decode tick. The window submits each request when it
falls due and calls `ServeEngine.step()` while anything is queued or
decoding; the harness stamps every token itself after each step. After the
window's last arrival the engine drains what is left, up to `drain_s`:
tokens stamped after the window close count toward latency but not toward
throughput, and a request that never completes counts as failed, with the
time until the drain gave up standing for its first token.

The check samples finished requests (the longest among them), then runs
the float32 reference over each prompt and its served tokens, layer by
layer with the weights drawn again from the seed, under the same plan, and
reads by how far each served token's logit lies below the reference's
best at its position.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, harness, reference, weights

CONTROLS = ("fp8", "int8")     # the reference's precisions calibrate.py reads


def warm_lengths(mix, block, bucket, coverage):
    """One prompt length for each pair (padded prefill length, prompt
    pages) the mix can produce: the prefill program's shape is the first,
    the page insert's the second."""
    b = -(-max(bucket, block) // block) * block
    seen = {}
    for p in range(mix["prompt"]["min"], mix["prompt"]["max"] + 1):
        key = (min(-(-p // b) * b, coverage), -(-p // block))
        seen.setdefault(key, p)
    return sorted(seen.values())


def build(run, seed, cfg):
    """Weights, plan and engine. Returns (engine, plan state, shapes)."""
    from repro.core.spion import SpionController, SpionState
    from repro.launch.serve import ServeEngine
    from repro.models.registry import build as build_model
    srv = run.cell.config["serving"]
    bundle = build_model(cfg)
    shapes = jax.eval_shape(bundle.init, jax.random.key(0))
    sp = dataclasses.replace(cfg.spion, min_dense_epochs=1,
                             max_dense_epochs=1)
    ctl = SpionController(sp, causal=cfg.causal, seq_len=srv["max_len"])
    state = SpionState()
    cap = ctl.capture_kwargs(state)
    capture = jax.jit(lambda p, b, f: bundle.forward(
        p, b, capture={"filt": f, "block": sp.block_size})[1]["captured"])
    # the plan from weights and a prompt drawn from the mix's plan seed:
    # every run seed serves under the same plan, so does the same work
    plan_seed = run.cell.traffic["plan_seed"]
    toks = np.random.default_rng(plan_seed).integers(
        0, cfg.vocab_size, (1, srv["max_len"])).astype(np.int32)
    pooled, frob = capture(weights.make(shapes, plan_seed),
                           {"tokens": jnp.asarray(toks)}, cap["filt"])
    state = ctl.observe_epoch(state, np.asarray(pooled), np.asarray(frob))
    params = weights.make(shapes, seed)
    if state.phase != "sparse":
        raise RuntimeError("the capture produced no plan")
    eng = ServeEngine(cfg, params, slots=srv["slots"],
                      max_len=srv["max_len"],
                      spion=ctl.attention_exec(state, phase="decode"),
                      prefill_bucket=srv["prefill_bucket"])
    return eng, state, shapes


def warm(eng, mix, cfg, seed):
    """A request of two tokens for each prompt shape the mix can produce,
    so that every program the window runs is compiled and loaded."""
    from repro.launch.serve import Request
    rng = np.random.default_rng(seed ^ 0x5EED)
    lens = warm_lengths(mix, eng.exec.block, eng.prefill_bucket,
                        eng.exec.coverage)
    for i, n in enumerate(lens):
        eng.run([Request(rid=-1 - i, prompt=rng.integers(
            0, cfg.vocab_size, n).astype(np.int32), max_new=2)])
    return lens


def window(run, eng, arrivals, seconds, mix, tracer):
    """The open loop. Returns per-request stamps and per-step records."""
    from repro.launch.serve import Request
    n = len(arrivals)
    stamps = {a.rid: [] for a in arrivals}
    reqs, refused, steps = {}, set(), []
    live, i = [], 0
    deadline = seconds + mix["drain_s"]
    tracing = tracer is not None
    t0 = time.perf_counter()
    if tracing:
        tracer.start()
    while True:
        now = time.perf_counter() - t0
        if tracing and now >= mix["trace_seconds"]:
            tracer.stop()
            run.record["trace_until"] = time.perf_counter() - t0
            tracing = False
        while i < n and arrivals[i].due <= now:
            a = arrivals[i]
            r = Request(rid=a.rid, prompt=a.prompt, max_new=a.max_new)
            reqs[a.rid] = r
            try:
                eng.submit(r)
                live.append(r)
            except ValueError:
                refused.add(a.rid)
            i += 1
        if not live:
            if i >= n:
                break
            with harness.span("bench.wait", tracing):
                time.sleep(max(arrivals[i].due - now, 0.0))
            continue
        if now > deadline:
            break
        before = [len(r.out) for r in live]
        with harness.span("bench.tick", tracing):
            eng.step()
        t = time.perf_counter() - t0
        admitted, positions = [], []
        for r, k in zip(live, before):
            new = len(r.out) - k
            if not new:
                continue
            stamps[r.rid] += [t] * new
            if k == 0:
                admitted.append(len(r.prompt))
            positions += [len(r.prompt) + j - 1
                          for j in range(max(k, 1), len(r.out))]
        steps.append({"t0": now, "t1": t, "admitted": admitted,
                      "positions": positions})
        live = [r for r in live if not r.done]
    if tracing:
        tracer.stop()
        run.record["trace_until"] = time.perf_counter() - t0
    end = time.perf_counter() - t0
    return reqs, stamps, refused, steps, end


def measure(run, arrivals, reqs, stamps, refused, seconds, end):
    """End-to-end numbers of the window. A request with no first token
    counts its wait until the drain gave up."""
    ttft, gaps, failed = [], [], 0
    for a in arrivals:
        s = stamps[a.rid]
        r = reqs.get(a.rid)
        ok = a.rid not in refused and r is not None and r.done
        failed += not ok
        ttft.append((s[0] if s else end) - a.due)
        gaps += list(np.diff(s))
    out_tokens = sum(sum(t <= seconds for t in s) for s in stamps.values())
    run.attempted, run.failed = len(arrivals), failed
    e2e = {"serve_output_tokens_per_s": out_tokens / seconds,
           "ttft_p95_ms": 1e3 * harness.nearest_rank(ttft, 0.95),
           "itl_p95_ms": 1e3 * harness.nearest_rank(gaps, 0.95)
           if gaps else 0.0}
    run.e2e.update(e2e)
    run.e2e["ttft_p95_ms.sat"] = run.e2e["ttft_p95_ms"]
    run.e2e["itl_p95_ms.sat"] = run.e2e["itl_p95_ms"]
    return e2e


def sample(reqs, mix, seed):
    """Finished requests to check: the longest, then others drawn from the
    seed, until `sample_tokens` served tokens are covered."""
    done = sorted((r for r in reqs.values() if r.done),
                  key=lambda r: (-(len(r.prompt) + len(r.out)), r.rid))
    if not done:
        return []
    rng = np.random.default_rng(seed ^ 0xC4EC)
    rest = [done[i] for i in rng.permutation(len(done) - 1) + 1]
    picked, total = [done[0]], len(done[0].out)
    for r in rest:
        if total >= mix["sample_tokens"] or len(picked) >= mix["sample_max"]:
            break
        picked.append(r)
        total += len(r.out)
    return picked


def weights_fn(shapes, seed):
    """name, layer -> float32 leaf, drawn again from the seed. The key is
    made once, outside the jitted draws, so one program per leaf serves
    every layer and every seed."""
    flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
    table = {weights.leaf_name(p): sd for p, sd in flat}
    key = weights.seed_key(seed)
    draw_layer = jax.jit(weights.layer_leaf, static_argnums=(1, 2, 3))
    draw_leaf = jax.jit(weights.unstacked_leaf, static_argnums=(1, 2, 3))

    def fn(name, layer):
        sd = table[name]
        if layer is None:
            x = draw_leaf(key, name, tuple(sd.shape), sd.dtype)
        else:
            x = draw_layer(key, name, tuple(sd.shape[1:]), sd.dtype, layer)
        return x.astype(jnp.float32)
    return fn


def reference_logits(run, picked, masks_b, shapes, seed, precisions):
    """Reference logits at every served token's position of the picked
    requests: {precision: (B, R, V)}, with the served tokens (B, R) and a
    (B, R) mask of real rows."""
    block = run.block
    seqs = [np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
            for r in picked]
    S = -(-max(len(s) for s in seqs) // block) * block
    R = max(len(r.out) for r in picked)
    toks = np.zeros((len(picked), S), np.int32)
    rows = np.zeros((len(picked), R), np.int32)
    served = np.zeros((len(picked), R), np.int32)
    real = np.zeros((len(picked), R), bool)
    for b, (r, s) in enumerate(zip(picked, seqs)):
        toks[b, :len(s)] = s
        P, k = len(r.prompt), len(r.out)
        rows[b, :k] = np.arange(P - 1, P - 1 + k)
        rows[b, k:] = P - 1 + k - 1
        served[b, :k] = r.out
        real[b, :k] = True
    nb = S // block
    vis = [reference.visible(m[:nb, :nb], S, True) for m in masks_b]
    masks = [v[0] for v in vis]
    logits = reference.decoder_logits(
        run.model, precisions, weights_fn(shapes, seed), jnp.asarray(toks),
        rows, masks, vis[0][1])
    return logits, served, real


def widest_gap(ref_logits, tokens, real):
    """max over real rows of (best reference logit - logit of `tokens`)."""
    best = jnp.max(ref_logits, -1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(tokens)[..., None],
                              -1)[..., 0]
    gap = np.asarray(best - got)
    return float(gap[real].max())


def readings(run, picked, masks_b, shapes, seed, control=False):
    """The served tokens' widest gap; with `control`, also the gap of the
    tokens that the float8 and the int8 reference put first, and of a
    served token altered."""
    precisions = ("f32",) + (CONTROLS if control else ())
    logits, served, real = reference_logits(run, picked, masks_b, shapes,
                                            seed, precisions)
    best = np.asarray(jnp.argmax(logits["f32"], -1))
    out = {"served_logit_gap": widest_gap(logits["f32"], served, real),
           "served_off_argmax": int((best != served)[real].sum())}
    if control:
        for p in CONTROLS:
            first = np.asarray(jnp.argmax(logits[p], -1))
            out[f"control_{p}.served_logit_gap"] = widest_gap(
                logits["f32"], first, real)
            out[f"control_{p}_off_argmax"] = int((best != first)[real].sum())
        altered = (served + 1) % run.model["vocab_size"]
        out["fault_token_altered.served_logit_gap"] = widest_gap(
            logits["f32"], altered, real)
    out["sampled_requests"] = len(picked)
    out["sampled_tokens"] = int(real.sum())
    return out


def run(run, *, seed, seconds, trace, t_process, compiles, control=False):
    from bench.spec import program_config
    cfg = program_config(run.cell.config)
    mix = run.cell.traffic
    eng, state, shapes = build(run, seed, cfg)
    harness.note("weights, plan and engine built", t_process)
    run.block = eng.exec.block
    harness.plan_line(run.cell, state.tables, run.block, state.density)
    run.plan_nvalid = list(np.asarray(state.tables["nvalid"]))
    masks_b = harness.block_masks(state.tables)
    arrivals = gen.chat_arrivals(mix, seed, seconds, cfg.vocab_size)
    run.record["warm_lengths"] = warm(eng, mix, cfg, seed)
    harness.note("warmed", t_process)
    run.record["setup_s"] = time.perf_counter() - t_process
    run.e2e["setup_s"] = run.record["setup_s"]

    compiles.armed = True
    tracer = harness.Tracer(run.cell.name) if trace else None
    reqs, stamps, refused, steps, end = window(run, eng, arrivals, seconds,
                                               mix, tracer)
    compiles.armed = False
    measure(run, arrivals, reqs, stamps, refused, seconds, end)
    harness.note(f"window and drain over ({end:.1f} s)", t_process)
    run.record.update(steps=steps, window_end_s=end,
                      window_compiles=compiles.count,
                      prefill_bucket=eng.prefill_bucket)
    run.memory_peak_bytes = harness.memory_peak()

    picked = sample(reqs, mix, seed)
    del eng
    gc.collect()
    lim = run.cell.limits["limits"]
    if picked:
        nums = readings(run, picked, masks_b, shapes, seed, control=control)
        harness.note("reference compared", t_process)
        run.record["readings"] = nums
    else:
        nums = {"served_logit_gap": float("inf")}
    run.checks = [(k, nums[k], lim[k]) for k in sorted(lim)]
    run.checks.append(("window_compiles", float(compiles.count), 0.0))
    if tracer:
        run.trace = tracer.read()
