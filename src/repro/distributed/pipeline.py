"""GPipe-style pipeline parallelism over a mesh axis (default: 'pod').

Stage weights are stacked on a leading dim sharded over the axis; microbatch
activations flow stage-to-stage via collective_permute inside shard_map.
JAX autodiff through the scan yields the backward schedule automatically
(GPipe semantics: full forward wave then backward wave; 1F1B is a further
scheduling optimisation, out of scope). Used for the 88-layer
mistral-large-123b config when pipeline_stages > 1 (DESIGN.md §3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(mesh: Mesh, axis: str, stage_fn, stage_params, x, n_micro: int):
    """Run `stage_fn(params_slice, act) -> act` as an S-stage pipeline.

    stage_params: pytree with leading dim S (= mesh.shape[axis]) on every leaf.
    x: (B, ...) batch, B divisible by n_micro; activation shape is preserved
    across stages. Returns (B, ...) outputs.
    """
    S = mesh.shape[axis]
    B = x.shape[0]
    mb = B // n_micro
    xs = x.reshape((n_micro, mb) + x.shape[1:])

    pspecs = jax.tree_util.tree_map(lambda _: P(axis), stage_params)

    def inner(params, xs):
        # params: leading dim 1 (this stage); xs: (n_micro, mb, ...) replicated
        idx = jax.lax.axis_index(axis)
        p_local = jax.tree_util.tree_map(lambda a: a[0], params)
        state = jnp.zeros(xs.shape[1:], xs.dtype)
        outputs = jnp.zeros_like(xs)

        def tick(carry, t):
            state, outputs = carry
            x_in = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, n_micro - 1), 0, keepdims=False)
            cur = jnp.where(idx == 0, x_in, state)
            y = stage_fn(p_local, cur)
            out_t = t - (S - 1)
            is_emit = (idx == S - 1) & (out_t >= 0)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(is_emit, y, jax.lax.dynamic_index_in_dim(
                    outputs, jnp.clip(out_t, 0, n_micro - 1), 0, keepdims=False)),
                jnp.clip(out_t, 0, n_micro - 1), 0)
            nxt = jax.lax.ppermute(y, axis, [(i, (i + 1) % S) for i in range(S)])
            return (nxt, outputs), None

        (_, outputs), _ = jax.lax.scan(tick, (state, outputs),
                                       jnp.arange(n_micro + S - 1))
        # only the last stage holds real outputs; broadcast to all stages
        outputs = jax.lax.psum(
            jnp.where(idx == S - 1, outputs, jnp.zeros_like(outputs)), axis)
        return outputs

    fn = jax.shard_map(inner, mesh=mesh,
                       in_specs=(pspecs, P()), out_specs=P(),
                       check_vma=False)
    out = fn(stage_params, xs)
    return out.reshape((B,) + x.shape[1:])
