"""The fused kernels compiled by Mosaic for one described TPU v5e chip, at
the widths the main path runs: spion-lra (head_dim 16, block 64, L 2048)
and qwen2-7b (head_dim 128, block 128, 7 query heads per KV head, L 4096,
causal, the depth-3 DMA ring; dK/dV also at depth 1, which has no
prologue). Interpret mode checks none of Mosaic's
tiling or VMEM rules; this compile does, with no chip attached.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import block_sparse_attn as bsa
from repro.kernels.dispatch import KernelConfig

SHAPES = {
    # name: (N, G, S, hd, block, K, dtype, causal)
    "spion-lra-f32": (8, 1, 2048, 16, 64, 8, jnp.float32, False),
    "spion-lra-bf16": (8, 1, 2048, 16, 64, 8, jnp.bfloat16, False),
    "qwen2-7b-bf16": (4, 7, 4096, 128, 128, 8, jnp.bfloat16, True),
}


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache without the chip: keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_call(kernel, shape, dev, depth=3):
    N, G, S, hd, block, K, dt, causal = SHAPES[shape]
    nrb = S // block

    def sds(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=dev)

    # the wrappers take operands lane-padded as _fused_op passes them
    hp = hd + -hd % bsa.LANES
    q, kv = sds((N, G, S, hp), dt), sds((N, S, hp), dt)
    col, nv = sds((nrb, K), jnp.int32), sds((nrb,), jnp.int32)
    lse = sds((N, G, S, 1), jnp.float32)
    kw = dict(block=block, causal=causal, sliding_window=None,
              interpret=False, config=KernelConfig(depth=depth), head_dim=hd)
    if kernel == "fwd":
        return (lambda *a: bsa._fused_forward(*a, **kw)), (q, kv, kv, col, nv)
    fn = bsa._fused_dq if kernel == "dq" else bsa._fused_dkv
    return (lambda *a: fn(*a, **kw)), (q, kv, kv, q, lse, lse, col, nv)


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
def test_kernel_compiles_for_v5e(one_chip, monkeypatch, kernel, shape):
    # the platform reads as CPU here; steer the kernels' Mosaic parameters
    # to the TPU branch
    monkeypatch.setattr(bsa, "compiled_backend", lambda: "tpu")
    fn, args = _kernel_call(kernel, shape, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel's own name (pallas_call's `name`) names its instruction
    assert f"%fused_{kernel}." in text


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_dkv_depth1_compiles_for_v5e(one_chip, monkeypatch, shape):
    # depth 1 has no prologue: each entry's fetch starts and is waited
    # inside the loop, whose trip count is the column's own nvalid_t
    monkeypatch.setattr(bsa, "compiled_backend", lambda: "tpu")
    fn, args = _kernel_call("dkv", shape, one_chip, depth=1)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and "%fused_dkv." in text
