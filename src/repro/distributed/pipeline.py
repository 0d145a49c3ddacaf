"""Pipeline parallelism: GPipe-style microbatched execution over a mesh
axis, built on shard_map + lax.ppermute.

The decoder's scanned stack is already stacked over layer repetitions
(reps, ...); ``pipeline_forward`` splits those reps into S contiguous
stages sharded over the ``stage`` mesh axis and streams M microbatches
through them.  Steady-state schedule (fill + M + drain slots):

    slot t: stage s runs microbatch (t - s) if 0 <= t - s < M
    activations move s -> s+1 between slots via collective-permute

ppermute is differentiable, so wrapping ``pipeline_forward`` in jax.grad
yields the standard GPipe backward (reverse permutes).  On a multi-pod
mesh this maps stages onto the 'pod' axis — the configuration exercised
in tests/test_pipeline.py (4 host devices).  Bubble fraction is the usual
(S-1)/(M+S-1); pick M >= 4*S for <20% bubble.
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def split_stages(stacked_params, n_stages: int):
    """(reps, ...) pytree -> (S, reps/S, ...) pytree."""

    def reshape(x):
        reps = x.shape[0]
        assert reps % n_stages == 0, f"{reps} reps across {n_stages} stages"
        return x.reshape(n_stages, reps // n_stages, *x.shape[1:])

    return jax.tree.map(reshape, stacked_params)


def pipeline_forward(stage_fn: Callable, staged_params, x: jax.Array, *,
                     mesh: Mesh, axis: str = "stage",
                     n_microbatches: int) -> jax.Array:
    """Run x through all stages with microbatch pipelining.

    stage_fn(params_one_rep, x) -> x  (applied rep-by-rep inside a stage)
    staged_params: pytree with leading dims (S, reps_per_stage, ...)
    x: (batch, ...) with batch % n_microbatches == 0.
    """
    n_stages = mesh.shape[axis]
    m = n_microbatches
    b = x.shape[0]
    assert b % m == 0
    mb = b // m
    micro = x.reshape(m, mb, *x.shape[1:])

    def stage_program(params_local, micro_local):
        # params_local: (1, reps_per_stage, ...); micro_local: (m, mb, ...)
        sidx = jax.lax.axis_index(axis)
        params_here = jax.tree.map(lambda p: p[0], params_local)

        def run_stage(xm):
            def body(carry, rep_params):
                return stage_fn(rep_params, carry), None
            out, _ = jax.lax.scan(body, xm, params_here)
            return out

        state = jnp.zeros_like(micro_local[0])
        outputs = jnp.zeros_like(micro_local)
        n_slots = m + n_stages - 1

        def slot(t, carry):
            state, outputs = carry
            # stage 0 ingests microbatch t; others use the permuted state
            feed_idx = jnp.clip(t, 0, m - 1)
            my_in = jnp.where(sidx == 0, micro_local[feed_idx], state)
            active = (t - sidx >= 0) & (t - sidx < m)
            out = run_stage(my_in)
            out = jnp.where(active, out, state)
            # the last stage records finished microbatch (t - S + 1)
            done_idx = jnp.clip(t - n_stages + 1, 0, m - 1)
            record = (sidx == n_stages - 1) & (t - sidx >= 0) & (t - sidx < m)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(record, out, outputs[done_idx]),
                done_idx, 0)
            # shift activations to the next stage
            state = jax.lax.ppermute(
                out, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return state, outputs

        state, outputs = jax.lax.fori_loop(0, n_slots, slot,
                                           (state, outputs))
        # only the last stage recorded non-zero outputs; make the result
        # identical on every shard so out_specs can be replicated
        return jax.lax.psum(outputs, axis)

    spec_params = jax.tree.map(lambda _: P(axis), staged_params)
    out = jax.shard_map(
        stage_program, mesh=mesh,
        in_specs=(spec_params, P()),        # microbatches replicated
        out_specs=P(),                       # only last stage's writes matter
        check_vma=False,
    )(staged_params, micro)
    return out.reshape(b, *x.shape[1:])
