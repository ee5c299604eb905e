"""Production mesh definitions.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax import).

Single pod:  (data=16, model=16)            = 256 chips (TPU v5e pod)
Multi-pod:   (pod=2, data=16, model=16)     = 512 chips
The `pod` axis composes with `data` for DP by default and can host pipeline
stages (distributed/pipeline.py).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis Auto (GSPMD-partitioned)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, seq: int = 1):
    """The pod meshes. `seq > 1` carves a sequence-parallel axis out of the
    data axis (chip count unchanged): long-context sparse training shards Q
    row-blocks over 'seq' with a pattern-bounded halo exchange
    (kernels/sharded.py, DESIGN.md §10) while dense ops keep GSPMD."""
    if seq > 1:
        if 16 % seq:
            raise ValueError(f"seq={seq} must divide the data axis (16)")
        shape = (2, seq, 16 // seq, 16) if multi_pod else (seq, 16 // seq, 16)
        axes = (("pod", "seq", "data", "model") if multi_pod
                else ("seq", "data", "model"))
        return make_mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_seq_mesh(seq: int, data: int = 1, model: int = 1):
    """Small explicit (seq, data, model) mesh — tests / virtual-device CI.
    Axes of size 1 are kept (the names drive the dispatch, not the sizes)
    except model, dropped when 1 to mirror the common 2-axis test meshes."""
    if model > 1:
        return make_mesh((seq, data, model), ("seq", "data", "model"))
    return make_mesh((seq, data), ("seq", "data"))


def make_host_mesh():
    """Whatever this host offers (CPU CI: 1 device) as a (data, model) mesh."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))


def make_distributed_mesh(*, model: int = 1, seq: int = 1,
                          data: int | None = None):
    """Process-spanning mesh for a `jax.distributed` job: the 'pod' axis is
    exactly the process axis (batch data-parallelism across hosts — the
    highest-latency fabric carries only the gradient all-reduce), and
    seq/data/model fill each process's local devices (seq/model collectives
    stay on the intra-host fabric).

    Built by reshaping `jax.devices()` directly rather than via
    mesh_utils.create_device_mesh: jax's global device order is
    process-major, so the leading reshape axis IS the process boundary —
    the property the single-controller broadcast, the checkpoint commit
    barrier and the halo-exchange locality analysis all assume. An ICI-
    optimising permutation that traded that alignment away for torus
    locality would silently put 'pod' neighbours on different hosts.

    Degrades cleanly to single-process (pod=1): the same axis names, so
    pspecs and dispatch decisions are identical between a CI virtual-device
    run and a real multi-host launch."""
    import numpy as np
    nproc = jax.process_count()
    nloc = jax.local_device_count()
    per = seq * model
    if data is None:
        if nloc % per:
            raise ValueError(
                f"local device count {nloc} not divisible by "
                f"seq*model={per}")
        data = nloc // per
    if seq * data * model != nloc:
        raise ValueError(
            f"seq*data*model = {seq}*{data}*{model} != local device "
            f"count {nloc}")
    devs = np.asarray(jax.devices())
    if seq > 1:
        shape, axes = ((nproc, seq, data, model),
                       ("pod", "seq", "data", "model"))
    else:
        shape, axes = (nproc, data, model), ("pod", "data", "model")
    from jax.sharding import Mesh
    return Mesh(devs.reshape(shape), axes)


# Hardware constants for the roofline (TPU v5e per chip)
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW_PER_LINK = 50e9        # B/s per link
