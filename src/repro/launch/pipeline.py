"""Optional pipeline parallelism (GPipe-style) via shard_map + ppermute.

Stages hold disjoint slices of the layer stack (in_specs shard the stacked
layer params over the ``stage`` mesh axis); microbatches flow through the
classic looped schedule: every tick each stage processes one activation and
collective-permutes it downstream.  Bubble fraction = (S-1)/(M+S-1).

This is a first-class feature for deployments where the model axis alone
cannot hold the layer stack; the 40-cell dry-run matrix uses DP x TP (+ pod
DP), and PP is validated separately (tests/test_pipeline.py) on small
meshes, as recorded in DESIGN.md §5.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe(
    stage_fn: Callable,
    mesh,
    axis: str = "stage",
):
    """Builds ``run(stage_params, microbatches) -> outputs``.

    stage_fn(lp, x) applies one stage's layer slice to activation x.
    stage_params: pytree with leading dim == n_stages (sharded over axis).
    microbatches: [M, mb, ...] (replicated input; stage 0 injects them).
    Returns outputs [M, mb, ...] (replicated).
    """
    n_stages = mesh.shape[axis]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def body(stage_params, xs):
        stage = jax.lax.axis_index(axis)
        sp = jax.tree.map(lambda a: a[0], stage_params)  # this stage's slice
        M = xs.shape[0]
        T = M + n_stages - 1
        state = jnp.zeros_like(xs[0])
        outputs = jnp.zeros_like(xs)

        def tick(t, carry):
            state, outputs = carry
            # stage 0 injects microbatch t (while available); others consume
            # the permuted activation from upstream
            inject = jnp.clip(t, 0, M - 1)
            x_in = jnp.where(stage == 0, xs[inject], state)
            y = stage_fn(sp, x_in)
            # the last stage emits microbatch (t - (S-1)) at tick t
            out_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            emit = jnp.logical_and(stage == n_stages - 1, t >= n_stages - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(emit, y, outputs[out_idx]),
                out_idx,
                0,
            )
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outputs)

        state, outputs = jax.lax.fori_loop(0, T, tick, (state, outputs))
        # replicate the last stage's outputs everywhere
        outputs = jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs))
        return jax.lax.psum(outputs, axis)

    def run(stage_params, microbatches):
        specs_params = jax.tree.map(lambda _: P(axis), stage_params)
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(specs_params, P()),
            out_specs=P(),
            check_vma=False,
        )(stage_params, microbatches)

    return run
