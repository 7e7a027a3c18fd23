"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Provided as a first-class feature for depth-dominated deployments (the
default assigned meshes are covered by FSDP×TP, so PP is opt-in): the layer
stack is split into S stages over a ``stage`` mesh axis; microbatches flow
through the classic GPipe schedule (S + M - 1 ticks), activations hop
between stages with ppermute.  Differentiable — jax.grad through the
shard_map gives the usual 1F1B-equivalent memory behaviour under remat.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def make_pipeline_fn(layer_fn: Callable, n_stages: int, n_micro: int,
                     mesh: Mesh, stage_axis: str = "stage"):
    """Builds pipelined_apply(stacked_params, x_microbatches).

    ``layer_fn(params_stage, x) -> x`` is one stage's computation;
    ``stacked_params`` leading dim = n_stages (sharded over the stage axis);
    ``x_microbatches`` (n_micro, mb, ...) replicated.

    Returns outputs (n_micro, mb, ...) — the last stage's results,
    broadcast to all stages (psum over one-hot so the caller can compute a
    loss anywhere).
    """

    def pipelined(params, xs):
        # inside shard_map: params leaves have leading dim 1 (this stage)
        sid = jax.lax.axis_index(stage_axis)
        p_local = jax.tree.map(lambda a: a[0], params)
        mb_shape = xs.shape[1:]
        state = jnp.zeros(mb_shape, xs.dtype)          # current in-flight mb
        outs = jnp.zeros_like(xs)
        n_ticks = n_stages + n_micro - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t (when valid)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            injected = jnp.where((sid == 0) & (t < n_micro),
                                 xs[mb_idx], state)
            y = layer_fn(p_local, injected)
            # last stage emits microbatch t-(S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            emit = (sid == n_stages - 1) & (t >= n_stages - 1)
            outs = jnp.where(
                (jnp.arange(n_micro) == out_idx)[:, None, None] & emit,
                y[None], outs)
            # rotate activations to the next stage
            state = jax.lax.ppermute(y, stage_axis, perm)
            return (state, outs), None

        (state, outs), _ = jax.lax.scan(tick, (state, outs),
                                        jnp.arange(n_ticks))
        # broadcast last stage's outputs everywhere (replicated out_spec)
        last = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return last

    return jax.shard_map(pipelined, mesh=mesh,
                         in_specs=(P(stage_axis), P()),
                         out_specs=P(), check_vma=False)


def split_microbatches(x: jnp.ndarray, n_micro: int) -> jnp.ndarray:
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    return x.reshape(n_micro, B // n_micro, *x.shape[1:])
