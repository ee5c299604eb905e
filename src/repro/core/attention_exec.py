"""SparseAttentionExec — the single owner of one resolved sparse-attention
execution (DESIGN.md §11).

Before this existed, the sparse-phase state was threaded per-callsite: the
BCSR/SparsityPlan arrays rode the step as a raw dict, the STATIC block/halo
scalars were re-closed-over by every step builder, and the kernel resolution
lived in models/attention while the dispatch statics lived in kernels/ops —
four places that had to agree. The exec centralises all of it:

  - `tables`  — the SparsityPlan array payload (col_idx / nvalid and, when
    plan-built, row_idx / nvalid_t), TRACED: they are step inputs.
  - `block`, `halo`, `phase`, `kernel`, `kernel_config` — STATIC metadata,
    carried as pytree aux_data. Passing an exec through `jax.jit` therefore
    keys the trace on them automatically: a new plan with a different halo
    (or a different autotuned kernel config) retraces the step without any
    caller-side bookkeeping (launch/train.Trainer used to track the halo by
    hand to know when to rebuild its jitted sparse step). `kernel_config`
    is the autotuner's per-pattern scheduling pick (kernels/autotune.py),
    resolved from the on-disk cache at construction when the tables are
    concrete — so both the training step and the serve engine hit tuned
    configs simply by building their exec outside jit.

`phase` is "train" | "prefill" | "decode". Train and prefill share
`attend()` (full-sequence block-sparse attention, fused-Pallas or jnp per
`resolve_kernel`); decode uses `decode()` — the pattern-bounded KV-cache
gather (core.sparse_attention.sparse_decode_attention) that turns the
layer-wise pattern into an inference win: the query position's row-block
selects a bounded set of cache column blocks, and only those are read.

The exec is registered as a pytree, so it can be a jitted-step argument, a
lax.scan can carry its stacked tables (`scan_tables()`), and sharding-spec
trees map over it leaf-wise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sparse_attention import (BCSR, PLAN_TABLE_KEYS,
                                         bcsr_attention,
                                         paged_sparse_decode_attention,
                                         sparse_decode_attention)

_PHASES = ("train", "prefill", "decode")


def resolve_kernel(cfg, batch: int, kv_heads: int, *, nrb=None, halo=None,
                   prefer=None) -> str:
    """What the sparse phase dispatches to at trace time ("fused"/"jnp").

    Mesh-aware: under an active multi-device mesh (distributed.sharding.
    current_mesh()) "auto" picks the shard_map-wrapped fused kernel whenever
    at least one kernel dim shards — batch over the data axes, KV heads
    over 'model' (kernel_shard_axes), or Q row-blocks over 'seq' when the
    pattern halo fits (`nrb` row-blocks + the plan's static `halo` extents,
    kernel_seq_axis) — so sparse training keeps the Pallas kernel and its
    sparse backward on pods instead of reverting to jnp gathers. This mesh
    branch is deliberately NOT gated on the TPU backend: CI's
    virtual-device meshes and the dry-run must exercise the exact
    production dispatch (shard_map + kernel), accepting the Pallas
    interpreter's speed off-TPU. When nothing divides, "auto" falls back to
    the jnp BCSR path (the GSPMD-compatible gather stand-in). With no mesh,
    "auto" is the Mosaic kernel on TPU whatever the local chip count, and
    the jnp path off TPU (the kernels have no other compiled lowering).
    `prefer` overrides
    cfg.spion.kernel (an exec pinned to one impl). Exposed separately so
    dry-runs and tests can record the resolution without tracing a step."""
    impl = prefer or getattr(cfg.spion, "kernel", "auto")
    if impl != "auto":
        return impl
    from repro.distributed.sharding import current_mesh
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1:
        from repro.distributed.sharding import (kernel_seq_axis,
                                                kernel_shard_axes)
        baxes, kv_ax = kernel_shard_axes(mesh, batch, kv_heads)
        seq_ax, _ = kernel_seq_axis(mesh, nrb, halo)
        return "fused" if (baxes or kv_ax or seq_ax) else "jnp"
    from repro.kernels.dispatch import compiled_backend
    return "fused" if compiled_backend() == "tpu" else "jnp"


@jax.tree_util.register_pytree_node_class
class SparseAttentionExec:
    """One phase's resolved sparse-attention execution. See module docstring.

    Construct via `coerce` (normalises the legacy tables-dict payload, an
    existing exec, or None), `from_plan` (a SparsityPlan), or directly with
    stacked arrays. `tables` values are stacked (Ly, ...) for the
    scan-over-layers model families; `attend`/`decode` consume the
    PER-LAYER slices the scan hands back (they read only the exec's static
    metadata, never `self.tables`, so closing the exec over a scan body
    does not haul the stacked arrays into every layer)."""

    def __init__(self, tables, *, block, halo=None, phase="train",
                 kernel=None, kernel_config=None):
        if phase not in _PHASES:
            raise ValueError(f"phase must be one of {_PHASES}, got {phase!r}")
        self.tables = {k: tables[k] for k in PLAN_TABLE_KEYS
                       if tables is not None and tables.get(k) is not None}
        self.block = int(block)
        self.halo = None if halo is None else (int(halo[0]), int(halo[1]))
        self.phase = phase
        self.kernel = kernel  # None -> defer to cfg.spion.kernel
        # the autotune cache is consulted HERE, once per exec construction
        # (kernels/autotune.py): a pure on-disk lookup keyed by the pattern
        # digest, never a sweep. The resolved KernelConfig rides the pytree
        # aux (static), so every jitted step using this exec — training and
        # serving alike — hits the tuned schedule without retracing per
        # step. Construction under jit (tracer tables, e.g. the legacy
        # dict payload crossing launch/steps._coerce_step_tables) skips
        # the lookup: no digest of a tracer, config stays as given.
        if kernel_config is None and self.tables and \
                not any(isinstance(v, jax.core.Tracer)
                        for v in self.tables.values()):
            from repro.kernels.autotune import lookup
            kernel_config = lookup(self.tables, self.block)
        self.kernel_config = kernel_config

    # -- pytree protocol (tables traced; everything else static) -------------

    def tree_flatten(self):
        keys = tuple(k for k in PLAN_TABLE_KEYS if k in self.tables)
        children = tuple(self.tables[k] for k in keys)
        return children, (keys, self.block, self.halo, self.phase,
                          self.kernel, self.kernel_config)

    @classmethod
    def tree_unflatten(cls, aux, children):
        keys, block, halo, phase, kernel, kernel_config = aux
        ex = cls.__new__(cls)
        ex.tables = dict(zip(keys, children))
        ex.block, ex.halo, ex.phase, ex.kernel = block, halo, phase, kernel
        ex.kernel_config = kernel_config
        return ex

    def __repr__(self):
        shapes = {k: getattr(v, "shape", None) for k, v in self.tables.items()}
        return (f"SparseAttentionExec(phase={self.phase!r}, block={self.block}, "
                f"halo={self.halo}, kernel={self.kernel!r}, "
                f"kernel_config={self.kernel_config!r}, tables={shapes})")

    # -- constructors ---------------------------------------------------------

    @classmethod
    def coerce(cls, spion, *, phase=None, kernel=None, kernel_config=None):
        """None | exec | tables-dict payload -> exec (or None).

        The dict form is the historical `spion=` payload: stacked (or
        per-layer) arrays plus a static int 'block' and optionally the
        static 'halo' pair. The int leaves must be concrete — a dict that
        crossed a jit boundary has tracer ints; convert to an exec BEFORE
        jitting (launch/steps does) or pass the exec itself through jit."""
        if spion is None:
            return None
        if isinstance(spion, cls):
            if phase is not None and spion.phase != phase:
                return cls(spion.tables, block=spion.block, halo=spion.halo,
                           phase=phase, kernel=kernel or spion.kernel,
                           kernel_config=kernel_config or spion.kernel_config)
            return spion
        return cls(spion, block=spion["block"], halo=spion.get("halo"),
                   phase=phase or "train", kernel=kernel,
                   kernel_config=kernel_config)

    @classmethod
    def from_plan(cls, plan, *, phase="train", kernel=None,
                  kernel_config=None):
        """From a core.sparse_attention.SparsityPlan (halo from its stats)."""
        return cls(plan.tables, block=plan.tables["block"],
                   halo=plan.stats.get("halo"), phase=phase, kernel=kernel,
                   kernel_config=kernel_config)

    # -- table views ----------------------------------------------------------

    def scan_tables(self):
        """Stacked per-layer arrays to ride a lax.scan over layers. Decode
        needs only the forward BCSR (the query row selects its column
        blocks); train/prefill also carry the plan's transposed tables for
        the fused dK/dV backward grid."""
        keys = ("col_idx", "nvalid") if self.phase == "decode" \
            else PLAN_TABLE_KEYS
        return {k: self.tables[k] for k in keys if k in self.tables}

    def layer(self, idx):
        """Per-layer (or per-app) slice of the stacked tables — for callers
        that index by a traced layer id (the hybrid shared-attention block)
        instead of scanning."""
        keys = self.scan_tables()
        return {k: jnp.take(v, idx, axis=0) for k, v in keys.items()}

    # -- execution ------------------------------------------------------------

    def attend(self, cfg, q, k, v, layer_tables):
        """Sparse train/prefill attention for ONE layer.

        layer_tables: this layer's slices of `scan_tables()` —
        col_idx (nrb, K), nvalid (nrb,), optionally row_idx/nvalid_t.
        Dispatch follows `resolve_kernel` (mesh-aware "auto"): the fused
        differentiable Pallas kernel — through the shard_map wrapper under
        a multi-device mesh — or the pure-jnp BCSR path. Both paths train:
        the fused kernel's backward is sparse too, which is what makes the
        sparse phase's speedup honest for training, not just inference."""
        bcsr = BCSR(layer_tables["col_idx"], layer_tables["nvalid"],
                    self.block, q.shape[1])
        impl = resolve_kernel(cfg, q.shape[0], k.shape[2],
                              nrb=q.shape[1] // self.block, halo=self.halo,
                              prefer=self.kernel)
        if impl == "fused":
            from repro.kernels.ops import spion_attention_kernel
            return spion_attention_kernel(cfg, q, k, v, bcsr,
                                          row_idx=layer_tables.get("row_idx"),
                                          nvalid_t=layer_tables.get("nvalid_t"),
                                          halo=self.halo,
                                          config=self.kernel_config)
        return bcsr_attention(cfg, q, k, v, bcsr)

    def attend_app(self, cfg, q, k, v, app_idx):
        """`attend` for the hybrid family's shared attention block: the
        stacked tables are indexed by the (traced) application index, not
        scanned."""
        return self.attend(cfg, q, k, v, self.layer(app_idx))

    def decode(self, cfg, q, k_cache, v_cache, pos, layer_tables, *,
               ring=False):
        """Sparse one-token decode for ONE layer: gather and attend over
        only the cache blocks this query position's pattern row lists
        (core.sparse_attention.sparse_decode_attention — same Alg. 6
        zero-corrected softmax as the sparse prefill, so decode logits
        match the prefill row). `pos` may be per-batch-row (B,). ring=True
        for sliding-window ring-buffer caches."""
        return sparse_decode_attention(
            cfg, q, k_cache, v_cache, pos, layer_tables["col_idx"],
            layer_tables["nvalid"], block=self.block, ring=ring)

    def decode_app(self, cfg, q, k_cache, v_cache, pos, app_idx, *,
                   ring=False):
        return self.decode(cfg, q, k_cache, v_cache, pos, self.layer(app_idx),
                           ring=ring)

    def decode_paged(self, cfg, q, kp, vp, layer, pos, page_table,
                     layer_tables, *, ring=False):
        """`decode` over a paged KV pool (core.kv_pool.PagedKVCache): the
        pattern's column blocks resolve through the request's page-table
        row, so the O(K*block) cache gather is pure page indirection. The
        pool's page size must equal the plan block — the alignment that
        makes pattern block ids and page-table coordinates the same thing.
        kp/vp are the (L, num_pages, block, KV, hd) pool arrays, `layer`
        the traced pool layer index, page_table (B, NB)."""
        if kp.shape[2] != self.block:
            raise ValueError(
                f"paged decode: pool page size {kp.shape[2]} != plan block "
                f"{self.block}; build the pool with page == block")
        return paged_sparse_decode_attention(
            cfg, q, kp, vp, layer, pos, page_table,
            layer_tables["col_idx"], layer_tables["nvalid"],
            page=self.block, ring=ring)

    def decode_paged_app(self, cfg, q, kp, vp, app_idx, pos, page_table, *,
                         ring=False):
        return self.decode_paged(cfg, q, kp, vp, app_idx, pos, page_table,
                                 self.layer(app_idx), ring=ring)

    # -- introspection --------------------------------------------------------

    @property
    def coverage(self) -> int:
        """Sequence positions the pattern tables cover (nrb * block)."""
        return int(self.tables["col_idx"].shape[-2]) * self.block
