"""Sharded fused sparse attention: shard_map dispatch correctness, the
mesh-aware "auto" resolution, loud-failure guards, and the sparse
train-step compile proof on a 2-axis (data, model) mesh.

All multi-device checks run in subprocesses with 4 fake host devices (jax
locks the device count at first init — same pattern as
tests/test_distributed.py)."""
import pytest
from conftest import run_subprocess_case as _run_sub

from repro.configs import get_config
from repro.distributed.sharding import kernel_shard_axes
from repro.launch.mesh import make_mesh
from repro.models.attention import resolve_sparse_kernel


def test_kernel_shard_axes_choice():
    mesh = make_mesh((1,), ("data",))  # single device: nothing to shard
    assert kernel_shard_axes(mesh, 8, 4) == (None, None)


def test_dispatch_not_keyed_on_model_config(rng):
    """The kernel jit is keyed only on (causal, sliding_window, block, fused,
    interpret) — unrelated ModelConfig changes (act_shard, ar_bf16, bench
    sweeps over d_ff) must NOT retrace it."""
    import jax
    import numpy as np

    from repro.core.sparse_attention import bcsr_from_blockmask
    from repro.kernels.ops import _dispatch, spion_attention_kernel

    cfg = get_config("spion-lra")
    S, block, hd = 64, 32, 16
    q = jax.random.normal(jax.random.key(0), (2, S, 2, hd))
    kv = jax.random.normal(jax.random.key(1), (2, S, 2, hd))
    mask = np.random.default_rng(0).random((2, 2)) < 0.9
    np.fill_diagonal(mask, True)
    b = bcsr_from_blockmask(mask, block)
    spion_attention_kernel(cfg, q, kv, kv, b, interpret=True)
    n0 = _dispatch._cache_size()
    for variant in (cfg.replace(act_shard="d"), cfg.replace(ar_bf16=True),
                    cfg.replace(d_ff=4096), cfg.replace(scan_unroll=8)):
        spion_attention_kernel(variant, q, kv, kv, b, interpret=True)
    assert _dispatch._cache_size() == n0, \
        "unrelated config fields retraced the kernel jit"
    # kernel statics still key it
    spion_attention_kernel(cfg.replace(causal=True), q, kv, kv, b,
                           interpret=True)
    assert _dispatch._cache_size() == n0 + 1


def test_resolve_sparse_kernel_meshless():
    cfg = get_config("spion-lra")
    # no mesh, CPU backend -> jnp (unchanged single-device behaviour)
    assert resolve_sparse_kernel(cfg, 4, 4) == "jnp"
    import dataclasses
    forced = cfg.replace(spion=dataclasses.replace(cfg.spion, kernel="fused"))
    assert resolve_sparse_kernel(forced, 4, 4) == "fused"


def test_resolve_sparse_kernel_meshless_tpu_any_chip_count(monkeypatch):
    """On a TPU with no mesh, "auto" is the Mosaic kernel however many chips
    the host has — never a silent jnp fallback."""
    import jax
    from repro.kernels import dispatch
    monkeypatch.setattr(dispatch, "compiled_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda *a: 4)
    assert resolve_sparse_kernel(get_config("spion-lra"), 4, 4) == "fused"


AXES_CODE = """
from repro.distributed.sharding import kernel_shard_axes, kernel_pspecs
from repro.launch.mesh import make_mesh
from jax.sharding import PartitionSpec as P
mesh = make_mesh((2, 2), ("data", "model"))
# batch and KV both divide -> both shard
assert kernel_shard_axes(mesh, 4, 2) == (("data",), "model")
# KV indivisible -> clean fallback to batch-only sharding
assert kernel_shard_axes(mesh, 4, 3) == (("data",), None)
# batch indivisible, KV divides -> model-only
assert kernel_shard_axes(mesh, 3, 2) == (None, "model")
# nothing divides
assert kernel_shard_axes(mesh, 3, 3) == (None, None)
q, kv, tab = kernel_pspecs(mesh, 4, 2)
assert q == P(("data",), "model", None, None, None)
assert kv == P(("data",), "model", None, None)
assert tab == P()
# pod composes with data greedily, dropping axes that stop dividing
mesh3 = make_mesh((2, 2, 1), ("pod", "data", "model"))
assert kernel_shard_axes(mesh3, 4, 4) == (("pod", "data"), None)
assert kernel_shard_axes(mesh3, 2, 4) == (("pod",), None)
print("OK")
"""


# forward + grads of the shard_map-fused path vs the jnp BCSR path (the
# tolerances of tests/test_kernels.py: fwd 2e-5, grads 1e-3), plus bitwise
# agreement of the sharded forward with the meshless fused kernel — the
# shard boundary must not change the math at all.
MATCH_CODE = """
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core.sparse_attention import (BCSR, bcsr_attention,
                                         bcsr_from_blockmask,
                                         build_sparsity_plan)
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_mesh
from repro.models.attention import resolve_sparse_kernel, spion_sparse_attention

mesh = make_mesh((2, 2), ("data", "model"))
S, block, hd, B = 128, 32, 32, 4
n = S // block
rng = np.random.default_rng(0)

# (causal, sliding_window, H, KV, with_plan):
#   - encoder, no plan
#   - causal + plan
#   - causal + sliding window + plan
#   - GQA with KV sharded over model (KV=2 divides |model|=2)
#   - GQA with KV UNsharded (KV=3 indivisible -> batch-only sharding)
CASES = [(False, None, 4, 4, False),
         (True, None, 4, 4, True),
         (True, 96, 2, 2, True),
         (True, None, 4, 2, True),
         (True, None, 3, 3, False)]

for causal, sw, H, KV, with_plan in CASES:
    cfg = get_config("spion-lra").replace(
        causal=causal, sliding_window=sw, num_heads=H, num_kv_heads=KV,
        spion=dataclasses.replace(get_config("spion-lra").spion,
                                  block_size=block))
    mask = rng.random((n, n)) < 0.5
    np.fill_diagonal(mask, True)
    b = bcsr_from_blockmask(mask, block)
    layer = {"col_idx": b.col_idx, "nvalid": b.nvalid, "block": block}
    if with_plan:
        p = build_sparsity_plan(b.col_idx, b.nvalid, block, ncb=n)
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
        assert resolve_sparse_kernel(cfg, B, KV) == "fused", (causal, H, KV)
        o_sh = spion_sparse_attention(cfg, q, k, v, layer)
        g_sh = jax.grad(lambda *a: loss(*a, "auto"), argnums=(0, 1, 2))(q, k, v)
    o_local = spion_sparse_attention(
        cfg.replace(spion=dataclasses.replace(cfg.spion, kernel="fused")),
        q, k, v, layer)
    o_jnp = bcsr_attention(cfg, q, k, v, BCSR(b.col_idx, b.nvalid, block, S))
    g_jnp = jax.grad(lambda *a: loss(*a, "jnp"), argnums=(0, 1, 2))(q, k, v)

    tag = f"causal={causal} sw={sw} H={H} KV={KV} plan={with_plan}"
    assert bool(jnp.all(o_sh == o_local)), f"sharded fwd not bitwise: {tag}"
    np.testing.assert_allclose(np.asarray(o_sh), np.asarray(o_jnp),
                               atol=2e-5, err_msg=f"fwd vs jnp: {tag}")
    for name, a, w in zip("qkv", g_sh, g_jnp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w), atol=1e-3,
                                   err_msg=f"d{name} vs jnp: {tag}")
print("OK")
"""


# loud-failure guards: a bare fused kernel call under a multi-device mesh,
# the forward-only 3-kernel pipeline, and forcing "fused" when no mesh axis
# divides must all raise instead of running silently replicated.
GUARD_CODE = """
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.core.sparse_attention import bcsr_from_blockmask
from repro.distributed.sharding import mesh_context
from repro.kernels.block_sparse_attn import fused_block_sparse_attention
from repro.kernels.ops import spion_attention_kernel
from repro.launch.mesh import make_mesh
from repro.models.attention import resolve_sparse_kernel, spion_sparse_attention

mesh = make_mesh((2, 2), ("data", "model"))
S, block, hd = 64, 32, 16
n = S // block
rng = np.random.default_rng(0)
mask = rng.random((n, n)) < 0.8
np.fill_diagonal(mask, True)
b = bcsr_from_blockmask(mask, block)
col = jnp.maximum(b.col_idx, 0)
cfg = get_config("spion-lra")
q = jax.random.normal(jax.random.key(0), (2, S, 2, hd))
kv = jax.random.normal(jax.random.key(1), (2, S, 2, hd))
q5 = jax.random.normal(jax.random.key(2), (4, 1, S, hd))
kv4 = jax.random.normal(jax.random.key(3), (4, S, hd))

with mesh_context(mesh):
    # bare kernel call: no shard_map wrapper -> loud failure
    try:
        fused_block_sparse_attention(q5, kv4, kv4, col, b.nvalid, block=block,
                                     interpret=True)
        raise SystemExit("bare fused call under mesh must raise")
    except RuntimeError as e:
        assert "shard_map" in str(e), e
    # the fused kernel is the only wrapper path — the legacy 3-kernel
    # escape hatch is gone for good
    try:
        spion_attention_kernel(cfg, q, kv, kv, b, fused=False, interpret=True)
        raise SystemExit("fused kwarg must no longer exist")
    except TypeError as e:
        assert "fused" in str(e), e
    # wrapper under mesh routes through shard_map and works
    out_m = spion_attention_kernel(cfg, q, kv, kv, b, interpret=True)
    assert out_m.shape == q.shape
    # nothing divides (B=3, KV=3 on a 2x2 mesh): auto falls back to jnp,
    # forcing fused raises
    q3 = jax.random.normal(jax.random.key(4), (3, S, 3, hd))
    kv3 = jax.random.normal(jax.random.key(5), (3, S, 3, hd))
    assert resolve_sparse_kernel(cfg, 3, 3) == "jnp"
    forced = cfg.replace(spion=dataclasses.replace(cfg.spion, kernel="fused"))
    layer = {"col_idx": b.col_idx, "nvalid": b.nvalid, "block": block}
    try:
        spion_sparse_attention(forced, q3, kv3, kv3, layer)
        raise SystemExit("forced fused with no shardable axis must raise")
    except RuntimeError as e:
        assert "no mesh axis" in str(e), e
# outside the mesh the same bare call works (single-shard op)
out = fused_block_sparse_attention(q5, kv4, kv4, col, b.nvalid, block=block,
                                   interpret=True)
assert out.shape == q5.shape
print("OK")
"""


# the sparse train step compiles on the 2-axis (data, model) production-mesh
# layout with the shard_map kernel visible in the lowered HLO, and the
# dry-run sparse cell records the mesh-aware resolution.
TRAIN_STEP_CODE = """
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step, spion_dryrun_tables
from repro.models.registry import build
from repro.optim import adamw_init

mesh = make_mesh((2, 2), ("data", "model"))
L, B = 64, 4
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
tables = spion_dryrun_tables(cfg, L)
step = make_train_step(cfg, spion=True, sparse_kernel="auto")
args = (params, opt, batch, jnp.int32(0), tables)
with mesh_context(mesh):
    jaxpr = str(jax.make_jaxpr(step)(*args))
    assert "shard_map" in jaxpr, "auto must route through shard_map"
    assert "pallas_call" in jaxpr, "auto must keep the Pallas kernel"
    lowered = jax.jit(step).lower(*args)
    hlo = lowered.as_text()
    # shard_map lowers through Shardy as a manual computation; on TPU the
    # kernel itself additionally lowers to a tpu_custom_call
    assert "sdy.manual_computation" in hlo, "shard_map missing from HLO"
    if jax.default_backend() == "tpu":
        assert "tpu_custom_call" in hlo
    lowered.compile()   # the compile-proof on the sharded mesh
    # one real step executes and trains through the sharded kernel
    p2, _, metrics = jax.jit(step)(*args)
    assert bool(jnp.isfinite(metrics["loss"]))
    delta = jax.tree_util.tree_reduce(
        lambda a, x: a + float(jnp.sum(jnp.abs(x))), jax.tree_util.tree_map(
            jnp.subtract, p2, params), 0.0)
    assert delta > 0.0, "params must move through the sharded sparse step"
print("OK")
"""


# every SPION-able model family threads the mesh-aware dispatch: the encdec
# decoder self-attention and the hybrid shared-attention block go through
# the same spion_sparse_attention, so under the mesh their sparse prefill
# must carry shard_map+pallas_call in the jaxpr, match the jnp path, and
# keep working plan-less (col_idx/nvalid only -> under-jit transpose
# fallback inside the shard).
FAMILIES_CODE = """
import dataclasses
import numpy as np
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.distributed.sharding import mesh_context
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_prefill_step, spion_dryrun_tables
from repro.models.registry import build

mesh = make_mesh((2, 2), ("data", "model"))
L, B = 64, 4
for arch in ("whisper-tiny", "zamba2-1.2b"):
    cfg = get_config(arch).reduced()
    cfg = cfg.replace(spion=dataclasses.replace(cfg.spion, enabled=True,
                                                block_size=16))
    n_spion = (max(cfg.num_layers // cfg.hybrid_attn_every, 1)
               if cfg.family == "hybrid" else cfg.num_layers)
    tables = spion_dryrun_tables(cfg, L, n_spion)
    bundle = build(cfg)
    params = bundle.init(jax.random.key(0))
    batch = {"tokens": jnp.zeros((B, L), jnp.int32)}
    if cfg.family in ("audio", "encdec"):
        batch["frames"] = jnp.zeros((B, L, cfg.d_model), cfg.dtype)
    prefill = make_prefill_step(cfg, spion=True)
    with mesh_context(mesh):
        jaxpr = str(jax.make_jaxpr(prefill)(params, batch, tables))
        assert "shard_map" in jaxpr and "pallas_call" in jaxpr, arch
        o_sh = jax.jit(prefill)(params, batch, tables)
        # plan-less fallback still runs through the sharded kernel
        base = {k: tables[k] for k in ("col_idx", "nvalid", "block")}
        o_base = jax.jit(prefill)(params, batch, base)
        cfgj = cfg.replace(spion=dataclasses.replace(cfg.spion, kernel="jnp"))
        o_jnp = jax.jit(make_prefill_step(cfgj, spion=True))(params, batch,
                                                             tables)
    np.testing.assert_allclose(np.asarray(o_sh, np.float32),
                               np.asarray(o_base, np.float32), atol=5e-2,
                               err_msg=f"plan vs plan-less: {arch}")
    np.testing.assert_allclose(np.asarray(o_sh, np.float32),
                               np.asarray(o_jnp, np.float32), atol=5e-2,
                               err_msg=f"sharded-fused vs jnp: {arch}")
print("OK")
"""


DRYRUN_CELL_CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, tempfile
import jax
jax.devices()   # lock the 4-device count before dryrun's 512 flag could bite
from repro.configs.base import SHAPES, ShapeSpec
from repro.configs import get_config
from repro.launch import dryrun
from repro.launch.mesh import make_mesh

SHAPES["tiny_train"] = ShapeSpec("tiny_train", 64, 4, "train")
cfg = get_config("spion-lra").reduced()
cfg = cfg.replace(num_heads=4, num_kv_heads=2, head_dim=16,
                  spion=dataclasses.replace(cfg.spion, block_size=16))
mesh = make_mesh((2, 2), ("data", "model"))
with tempfile.TemporaryDirectory() as d:
    rec = dryrun.run_cell("spion-lra", "tiny_train", False, "sparse", d,
                          verbose=False, cfg_override=cfg, skip_costs=True,
                          mesh_override=mesh)
assert rec["status"] == "ok", rec
assert rec["sparse_kernel"] == "fused", rec
print("OK")
"""


@pytest.mark.parametrize("code", [AXES_CODE, MATCH_CODE, GUARD_CODE,
                                  TRAIN_STEP_CODE, FAMILIES_CODE,
                                  DRYRUN_CELL_CODE],
                         ids=["axes", "match", "guards", "train_step",
                              "families", "dryrun_cell"])
def test_sharded_subprocess(code):
    assert "OK" in _run_sub(code)
