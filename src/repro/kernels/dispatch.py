"""Platform-aware kernel dispatch knobs.

`interpret=None` everywhere in this package means "resolve from the
platform": on TPU the Pallas kernels compile through Mosaic, and the
pure-Python interpreter runs them only where no accelerator is attached
(CPU CI, dev laptops), so the same call sites run unchanged everywhere.
Pass an explicit bool to override. GPU counts as an accelerator too, but
the kernels use TPU-only primitives and have no GPU lowering: a GPU host
fails loudly at compile time instead of dropping to a silent 100x-slower
interpreter.

Also home of `KernelConfig` — the hashable per-kernel tuning knob bundle
(DMA pipeline depth, Mosaic dimension semantics) swept by
kernels/autotune.py and threaded as a jit-static through
ops/block_sparse_attn/sharded. It lives here, not in autotune.py,
because block_sparse_attn needs the type and autotune imports
block_sparse_attn (import-acyclic).

Also home of the shard_map-body marker: `pallas_call` has no GSPMD
partitioning rule, so under a multi-device mesh the fused kernel is only
correct inside the shard_map wrapper (kernels/sharded.py). The wrapper
flags its body trace with `sharded_body()`; `fused_block_sparse_attention`
checks `in_sharded_body()` and fails loudly instead of letting GSPMD run
the kernel fully replicated on every device. (Lives here, not in
sharded.py, to keep block_sparse_attn <-> sharded import-acyclic.)
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools

import jax

_IN_SHARDED_BODY: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "repro_in_sharded_kernel_body", default=False)

# accelerator backends: interpret=None never resolves to the interpreter
# there. Everything else (cpu, METAL, ...) runs the interpreter.
COMPILED_BACKENDS = ("tpu", "gpu", "cuda", "rocm")


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """One point in the fused-kernel tuning space (kernels/autotune.py).

    Hashable and immutable: it rides jit static_argnames, the _fused_op
    lru_cache key, and SparseAttentionExec pytree aux, so two execs with
    different tuned configs trace separately and identical configs share
    the compiled kernel.

      depth               K/V (bwd: Q/dO/lse/delta) DMA pipeline depth in
                          block_sparse_attn — 1 is a synchronous fetch,
                          2 the classic double buffer, 3+ deeper rings.
      dimension_semantics Mosaic grid annotation for the fwd/dQ grids
                          (None -> all-parallel). The dK/dV grid pins its
                          own (g must stay sequential for the scratch
                          accumulators).

    Changing a config can only ever change SPEED: every field controls
    scheduling (prefetch distance, grid parallelism), never
    the operation order inside a block, so tuned and default outputs are
    bitwise identical (tests/test_autotune.py holds this line).
    """
    depth: int = 2
    dimension_semantics: tuple | None = None

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        if self.dimension_semantics is not None:
            d["dimension_semantics"] = list(self.dimension_semantics)
        return d

    @classmethod
    def from_json(cls, d: dict) -> "KernelConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        unknown = set(d) - fields
        if unknown:
            raise ValueError(f"unknown KernelConfig fields: {sorted(unknown)}")
        kw = dict(d)
        if kw.get("dimension_semantics") is not None:
            kw["dimension_semantics"] = tuple(kw["dimension_semantics"])
        cfg = cls(**kw)
        if not isinstance(cfg.depth, int) or cfg.depth < 1:
            raise ValueError(f"KernelConfig.depth must be an int >= 1, "
                             f"got {cfg.depth!r}")
        return cfg


DEFAULT_CONFIG = KernelConfig()


@contextlib.contextmanager
def sharded_body():
    """Mark the current (trace-time) scope as inside the shard_map wrapper."""
    tok = _IN_SHARDED_BODY.set(True)
    try:
        yield
    finally:
        _IN_SHARDED_BODY.reset(tok)


def in_sharded_body() -> bool:
    return _IN_SHARDED_BODY.get()


@functools.lru_cache(maxsize=1)
def _platform_interpret() -> bool:
    # interpret only where there is no accelerator: a GPU host must fail
    # loudly at lowering, not fall back to the interpreter.
    return jax.default_backend() not in COMPILED_BACKENDS


def compiled_backend() -> str | None:
    """The accelerator name for this host ("tpu" / "gpu") or None.

    cuda/rocm normalise to "gpu"."""
    backend = jax.default_backend()
    if backend == "tpu":
        return "tpu"
    if backend in ("gpu", "cuda", "rocm"):
        return "gpu"
    return None


def is_compiled_backend() -> bool:
    return compiled_backend() is not None


def default_interpret(interpret=None) -> bool:
    """Resolve a tri-state interpret flag (None -> platform default)."""
    if interpret is None:
        return _platform_interpret()
    return bool(interpret)
