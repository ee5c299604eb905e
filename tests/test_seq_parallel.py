"""Sequence-parallel sparse attention (DESIGN.md §10): the pattern-bounded
halo exchange — seq-axis choice rules, shard_map correctness vs the meshless
fused kernel and the jnp BCSR path, the loud too-wide fallback, the
train-step compile proof on a (seq, data) mesh, and the sharded-op cache
regression (mesh identity keyed by descriptor, not the live object).

All multi-device checks run in subprocesses with 4 fake host devices (jax
locks the device count at first init — same pattern as
tests/test_sharded_attention.py)."""
import pytest

from conftest import run_subprocess_case as _run_sub


# seq-axis fit rules: divisibility, single-neighbour halos, no ring-wrap
# aliasing; plus the seq-mesh constructors.
AXES_CODE = """
from repro.distributed.sharding import kernel_pspecs_from_axes, kernel_seq_axis
from repro.launch.mesh import make_production_mesh, make_seq_mesh
from jax.sharding import PartitionSpec as P

mesh = make_seq_mesh(2, 2)
assert dict(mesh.shape) == {"seq": 2, "data": 2}
# fits: nrb=8 over 2 shards (W=4), halo (2,1)
ax, why = kernel_seq_axis(mesh, 8, (2, 1))
assert ax == "seq", why
# no halo supplied (plan-less tables without extents)
ax, why = kernel_seq_axis(mesh, 8, None)
assert ax is None and "halo" in why
# nrb not divisible
ax, why = kernel_seq_axis(mesh, 7, (1, 1))
assert ax is None and "divisible" in why
# halo exceeds the shard width (single-neighbour exchange impossible)
ax, why = kernel_seq_axis(mesh, 8, (5, 0))
assert ax is None and "shard width" in why
# ring-wrap aliasing: h_l + h_r > (n-1) * W
ax, why = kernel_seq_axis(mesh, 8, (4, 3))
assert ax is None and "alias" in why
# no seq axis at all
from repro.launch.mesh import make_mesh
ax, why = kernel_seq_axis(make_mesh((2, 2), ("data", "model")), 8, (1, 1))
assert ax is None and "no 'seq' axis" in why
# pspec layout with a seq axis
q, kv, tab = kernel_pspecs_from_axes(("data",), None, "seq")
assert q == P(("data",), None, None, "seq", None)
assert kv == P(("data",), None, "seq", None)
assert tab == P()
print("OK")
"""


# seq-sharded fused forward must be BITWISE identical to the meshless fused
# kernel (each row-block streams the same tiles in the same order — the halo
# exchange only relocates the data), and fwd+grads must match the jnp BCSR
# path at the tests/test_kernels.py tolerances. Cases: encoder, causal,
# causal+sliding-window, GQA, plan-less (forward-built local transpose).
MATCH_CODE = """
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core.sparse_attention import (BCSR, bcsr_attention,
                                         bcsr_from_blockmask,
                                         build_sparsity_plan)
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_seq_mesh
from repro.models.attention import resolve_sparse_kernel, spion_sparse_attention

mesh = make_seq_mesh(2, 2)
S, block, hd, B = 128, 16, 16, 4
n = S // block
rng = np.random.default_rng(0)

# (causal, sliding_window, H, KV, with_plan)
CASES = [(False, None, 4, 4, True),
         (True, None, 4, 4, False),
         (True, 48, 2, 2, True),
         (True, None, 4, 2, True),
         (False, None, 4, 2, False)]

for causal, sw, H, KV, with_plan in CASES:
    cfg = get_config("spion-lra").replace(
        causal=causal, sliding_window=sw, num_heads=H, num_kv_heads=KV,
        spion=dataclasses.replace(get_config("spion-lra").spion,
                                  block_size=block))
    # near-diagonal band pattern (extent <= 2): the flood-fill shape the
    # halo exchange targets
    mask = np.zeros((n, n), bool)
    for r in range(n):
        for c in range(max(r - 2, 0), min(r + 3, n)):
            mask[r, c] = rng.random() < 0.7
        mask[r, r] = True
    if causal:
        mask = np.tril(mask)
    b = bcsr_from_blockmask(mask, block)
    p = build_sparsity_plan(b.col_idx, b.nvalid, block, ncb=n)
    halo = tuple(p.stats["halo"])
    layer = {"col_idx": b.col_idx, "nvalid": b.nvalid, "block": block,
             "halo": halo}
    if with_plan:
        layer["row_idx"] = p.tables["row_idx"][0]
        layer["nvalid_t"] = p.tables["nvalid_t"][0]
    key = jax.random.key(hash((causal, H, KV)) % 1000)
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, hd))
    gout = jax.random.normal(jax.random.fold_in(key, 3), (B, S, H, hd))

    def loss(q, k, v, impl):
        c = cfg.replace(spion=dataclasses.replace(cfg.spion, kernel=impl))
        return jnp.sum(spion_sparse_attention(c, q, k, v, layer) * gout)

    with mesh_context(mesh):
        assert resolve_sparse_kernel(cfg, B, KV, nrb=n, halo=halo) == "fused"
        o_sh = spion_sparse_attention(cfg, q, k, v, layer)
        g_sh = jax.grad(lambda *a: loss(*a, "auto"), argnums=(0, 1, 2))(q, k, v)
    local = {k_: v_ for k_, v_ in layer.items() if k_ != "halo"}
    o_local = spion_sparse_attention(
        cfg.replace(spion=dataclasses.replace(cfg.spion, kernel="fused")),
        q, k, v, local)
    o_jnp = bcsr_attention(cfg, q, k, v, BCSR(b.col_idx, b.nvalid, block, S))
    g_jnp = jax.grad(lambda *a: loss(*a, "jnp"), argnums=(0, 1, 2))(q, k, v)

    tag = f"causal={causal} sw={sw} H={H} KV={KV} plan={with_plan} halo={halo}"
    assert bool(jnp.all(o_sh == o_local)), f"seq-sharded fwd not bitwise: {tag}"
    np.testing.assert_allclose(np.asarray(o_sh), np.asarray(o_jnp),
                               atol=2e-5, err_msg=f"fwd vs jnp: {tag}")
    for name, a, w in zip("qkv", g_sh, g_jnp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=1e-3,
                                   err_msg=f"d{name} vs jnp: {tag}")
print("OK")
"""


# too-wide patterns: a global vertical stripe makes the halo exceed the
# shard width -> loud fallback to batch/KV sharding (warning, no ppermute),
# and a hard error when nothing else shards; "auto" resolves to jnp when the
# seq axis is the only candidate and the pattern is too wide.
FALLBACK_CODE = """
import dataclasses, warnings
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core.sparse_attention import bcsr_from_blockmask, build_sparsity_plan
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_seq_mesh
from repro.kernels.sharded import sharded_fused_attention
from repro.models.attention import resolve_sparse_kernel

S, block, hd = 128, 16, 16
n = S // block
mask = np.zeros((n, n), bool)
np.fill_diagonal(mask, True)
mask[:, 0] = True                       # global-attention stripe
b = bcsr_from_blockmask(mask, block)
p = build_sparsity_plan(b.col_idx, b.nvalid, block, ncb=n)
halo = tuple(p.stats["halo"])
assert halo[0] == n - 1, halo           # stripe -> full left extent
col = jnp.maximum(b.col_idx, 0)
mesh = make_seq_mesh(2, 2)
B, KV, G = 4, 1, 1
q = jax.random.normal(jax.random.key(0), (B, KV, G, S, hd))
k = jax.random.normal(jax.random.key(1), (B, KV, S, hd))
v = jax.random.normal(jax.random.key(2), (B, KV, S, hd))

with mesh_context(mesh):
    # batch still shards -> warn + fall back, and the jaxpr must NOT carry
    # a halo exchange (no silent full-sequence ppermute)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        jaxpr = str(jax.make_jaxpr(lambda q, k, v: sharded_fused_attention(
            mesh, q, k, v, col, b.nvalid, block=block, interpret=True,
            halo=halo))(q, k, v))
    assert any("falls back to batch/KV" in str(x.message) for x in w), \
        [str(x.message) for x in w]
    assert "ppermute" not in jaxpr and "shard_map" in jaxpr
    # batch indivisible too -> actionable error, not silent replication
    q3 = jax.random.normal(jax.random.key(3), (3, KV, G, S, hd))
    k3 = jax.random.normal(jax.random.key(4), (3, KV, S, hd))
    try:
        sharded_fused_attention(mesh, q3, k3, k3, col, b.nvalid, block=block,
                                interpret=True, halo=halo)
        raise SystemExit("too-wide pattern with nothing else sharding must raise")
    except RuntimeError as e:
        assert "cannot seq-shard" in str(e) and "halo" in str(e), e
    # "auto" resolution: seq-only mesh + too-wide pattern -> jnp
    cfg = get_config("spion-lra")
    assert resolve_sparse_kernel(cfg, 3, 1, nrb=n, halo=halo) == "jnp"
    assert resolve_sparse_kernel(cfg, 3, 1, nrb=n, halo=(1, 0)) == "fused"
print("OK")
"""


# the sparse train step compiles and runs on a (seq=2, data=2) mesh with the
# halo exchange visible in the jaxpr (ppermute) and the lowered module
# (collective_permute + the shard_map manual-partitioning marker).
TRAIN_STEP_CODE = """
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_seq_mesh
from repro.launch.steps import make_train_step, spion_dryrun_tables
from repro.models.registry import build
from repro.optim import adamw_init

mesh = make_seq_mesh(2, 2)
L, B = 128, 4
cfg = get_config("spion-lra").reduced()
cfg = cfg.replace(num_heads=4, num_kv_heads=2, head_dim=16,
                  spion=dataclasses.replace(cfg.spion, block_size=16))
bundle = build(cfg)
params = jax.tree_util.tree_map(
    lambda x: x.astype(jnp.float32) if x.ndim >= 2 else x,
    bundle.init(jax.random.key(0)))
opt = adamw_init(params)
batch = {"tokens": jnp.zeros((B, L), jnp.int32),
         "labels": jnp.zeros((B, L), jnp.int32)}
tables = spion_dryrun_tables(cfg, L, max_extent=2)
assert tables["halo"] and max(tables["halo"]) <= 2, tables["halo"]
step = make_train_step(cfg, spion=True, sparse_kernel="auto",
                       halo=tables["halo"])
args = (params, opt, batch, jnp.int32(0), tables)
with mesh_context(mesh):
    jaxpr = str(jax.make_jaxpr(step)(*args))
    assert "shard_map" in jaxpr and "pallas_call" in jaxpr
    assert "ppermute" in jaxpr, "halo exchange missing from the jaxpr"
    lowered = jax.jit(step).lower(*args)
    hlo = lowered.as_text()
    assert "sdy.manual_computation" in hlo, "shard_map missing from HLO"
    assert "collective_permute" in hlo, "halo exchange missing from HLO"
    lowered.compile()
    p2, _, metrics = jax.jit(step)(*args)
    assert bool(jnp.isfinite(metrics["loss"]))
    delta = jax.tree_util.tree_reduce(
        lambda a, x: a + float(jnp.sum(jnp.abs(x))), jax.tree_util.tree_map(
            jnp.subtract, p2, params), 0.0)
    assert delta > 0.0, "params must move through the seq-sharded step"
print("OK")
"""


# regression for the sharded-op cache: keyed on the mesh DESCRIPTOR, so
# re-creating an identical mesh (tests, serve restarts, remesh after fault
# recovery) reuses the entry instead of retaining every Mesh object forever.
CACHE_CODE = """
import numpy as np
import jax, jax.numpy as jnp
from repro.core.sparse_attention import bcsr_from_blockmask
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_mesh
from repro.kernels import sharded
from repro.kernels.sharded import _op_cache_size, sharded_fused_attention

S, block, hd = 64, 16, 8
n = S // block
mask = np.eye(n, dtype=bool)
b = bcsr_from_blockmask(mask, block)
col = jnp.maximum(b.col_idx, 0)
q = jax.random.normal(jax.random.key(0), (4, 1, 1, S, hd))
k = jax.random.normal(jax.random.key(1), (4, 1, S, hd))

def call(mesh):
    with mesh_context(mesh):
        return sharded_fused_attention(mesh, q, k, k, col, b.nvalid,
                                       block=block, interpret=True)

m1 = make_mesh((2, 2), ("data", "model"))
call(m1)
n1 = _op_cache_size()
assert n1 >= 1
# an IDENTICAL mesh (fresh object) must hit the same cache entry
for _ in range(3):
    call(make_mesh((2, 2), ("data", "model")))
assert _op_cache_size() == n1, "identical meshes must not grow the op cache"
# a different mesh shape is a different entry
call(make_mesh((4,), ("data",)))
assert _op_cache_size() == n1 + 1
# and the cache is LRU-bounded as a churn backstop
sharded._OP_CACHE_MAX = n1 + 1
call(make_mesh((2, 2), ("data", "model")))   # reuse, no eviction needed
assert _op_cache_size() <= n1 + 1
print("OK")
"""


# a sparse dry-run cell must compile on a (seq, data) mesh (param sharding
# rules name 'model' unconditionally — sanitize_spec drops mesh-absent
# axes) and record the seq-sharding decision with its reason.
DRYRUN_CELL_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, tempfile
import jax
jax.devices()   # lock the 4-device count before dryrun's 512 flag could bite
from repro.configs.base import SHAPES, ShapeSpec
from repro.configs import get_config
from repro.launch import dryrun
from repro.launch.mesh import make_seq_mesh

SHAPES["tiny_train"] = ShapeSpec("tiny_train", 128, 4, "train")
cfg = get_config("spion-lra").reduced()
cfg = cfg.replace(num_heads=4, num_kv_heads=2, head_dim=16,
                  spion=dataclasses.replace(cfg.spion, block_size=16))
with tempfile.TemporaryDirectory() as d:
    rec = dryrun.run_cell("spion-lra", "tiny_train", False, "sparse", d,
                          verbose=False, cfg_override=cfg, skip_costs=True,
                          mesh_override=make_seq_mesh(2, 2))
assert rec["status"] == "ok", rec
assert rec["sparse_kernel"] == "fused", rec
seq = rec["seq_sharded"]
# the default dryrun pattern has global verticals -> too wide, recorded so
assert seq["active"] is False and seq["halo"] and "halo" in seq["detail"], seq
print("OK")
"""


@pytest.mark.parametrize("code", [AXES_CODE, MATCH_CODE, FALLBACK_CODE,
                                  TRAIN_STEP_CODE, CACHE_CODE,
                                  DRYRUN_CELL_CODE],
                         ids=["axes", "match", "fallback", "train_step",
                              "cache", "dryrun_cell"])
def test_seq_parallel_subprocess(code):
    assert "OK" in _run_sub(code)
