"""Continuous-batching serving: a reduced qwen2-7b-family model answering
more requests than it has cache slots — fused prefill straight into slots,
one batched decode per tick at per-slot positions, admission mid-decode —
then the same batch served SPARSELY from a SPION-style plan (decode gathers
only the pattern-listed KV-cache blocks), then the paged-cache payoff: a
SHARED SYSTEM PROMPT prefilled once and copy-on-write-mapped into every
later request (DESIGN.md §14).

    PYTHONPATH=src python examples/serve_batch.py
"""
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import Request, ServeEngine
from repro.models.registry import build


def make_requests(cfg, rng, n):
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(4, 12)))
                    .astype(np.int32),
                    max_new=12) for i in range(n)]


def full_causal_tables(layers, nrb, block):
    """A fully-covering stand-in plan (a real run would use the trained
    SparsityPlan: SpionController.attention_exec(state, phase='decode'))."""
    from repro.launch.steps import causal_band_tables
    return dict(causal_band_tables(layers, nrb), block=block)


def serve(eng, reqs, label):
    t0 = time.time()
    eng.run(reqs)
    dt = time.time() - t0
    total = sum(len(r.out) for r in reqs)
    for r in reqs:
        print(f"  req {r.rid}: P={len(r.prompt)} -> {r.out}")
    print(f"  [{label}] {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s batched on CPU)\n")


def main():
    enable_compile_cache()
    cfg = get_config("qwen2-7b").reduced().replace(remat=False)
    bundle = build(cfg)
    params = bundle.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    max_len, slots = 64, 4

    print(f"{slots} slots, 6 requests (mixed lengths; slots are reused):")
    serve(ServeEngine(cfg, params, slots=slots, max_len=max_len),
          make_requests(cfg, rng, 6), "dense decode")

    tabs = full_causal_tables(cfg.num_layers, max_len // 8, 8)
    print("same engine, sparse decode from a covering plan (logits match the\n"
          "dense path to kernel tolerance; greedy tokens can still diverge on\n"
          "random bf16 weights once one near-tie flips):")
    serve(ServeEngine(cfg, params, slots=slots, max_len=max_len, spion=tabs),
          make_requests(cfg, np.random.default_rng(0), 6), "sparse decode")

    # paged cache + COW prefix sharing: every request carries the same
    # 32-token system prompt; the engine prefills it ONCE, later requests
    # incref the same physical pages and only their private suffix is
    # computed (an exact repeat reuses the cached first token outright)
    print("shared system prompt across 5 requests (paged cache, COW):")
    sys_prompt = rng.integers(0, cfg.vocab_size, 32).astype(np.int32)
    reqs = [Request(rid=i,
                    prompt=np.concatenate(
                        [sys_prompt,
                         rng.integers(0, cfg.vocab_size,
                                      int(rng.integers(2, 6))).astype(np.int32)]),
                    max_new=12) for i in range(5)]
    eng = ServeEngine(cfg, params, slots=slots, max_len=max_len,
                      page_size=8)        # 32-token prefix = 4 shared pages
    serve(eng, reqs, "paged + shared prefix")
    st = eng.prefix_stats
    print(f"  prefix hit rate {st['prefix_hit_rate']:.2f} "
          f"({st['hits']}/{st['lookups']} page lookups), "
          f"{st['prefill_fused']} fused prefill(s) for 5 requests, "
          f"{st['prefix_tokens_reused']} prompt tokens reused, "
          f"{st['forks']} COW fork(s)")


if __name__ == "__main__":
    main()
