"""GPipe-style pipeline parallelism over ``shard_map`` + collective_permute.

The layer stack is split into ``n_stages`` contiguous stages laid out on a
``stage`` mesh axis.  Microbatches stream through the stages with the
classic GPipe schedule: ``n_micro + n_stages - 1`` ticks, activations
hopping stage->stage+1 through ``jax.lax.ppermute`` each tick (on TPU this
lowers to neighbor collective-permute on the ICI ring — the
double-buffering step applied across chips: stage s computes microbatch m
while its previous output (m-1) is in flight to stage s+1).

This module is deliberately model-agnostic: it pipelines any
``stage_fn(stage_params, x) -> x`` whose stages have identical activation
shapes (true for homogeneous decoder stacks).  The LM integration test
builds a toy stack and checks pipeline == sequential exactly; the
production configs default to DP/FSDP/TP (DESIGN.md §5) with PP available
as a config knob for the 88L/96L dense giants.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from jax.experimental.shard_map import shard_map


def pipeline_apply(stage_params, x_micro, *, stage_fn, mesh: Mesh,
                   axis: str = "stage"):
    """Run the pipelined stack.

    stage_params: pytree whose leaves have a leading ``n_stages`` dim,
        sharded one-stage-per-device-row along ``axis``.
    x_micro: (n_micro, micro_batch, ...) activations (replicated entry).
    stage_fn(params_slice, x) -> y, applied by every stage to its resident
        microbatch each tick.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stages - 1
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_stage(params, xs):
        # params: (1, ...) slice for this stage; xs: full (n_micro, ...)
        # (microbatch stream is replicated into every stage; stage 0 is the
        # only consumer — the others overwrite their buffer via ppermute).
        sidx = jax.lax.axis_index(axis)
        p = jax.tree.map(lambda t: t[0], params)
        # mark the carries as stage-varying (each stage holds different
        # data)
        buf = jax.lax.pcast(jnp.zeros_like(xs[0]), (axis,), to="varying")
        outs = jax.lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            m_in = jnp.clip(t, 0, n_micro - 1)
            buf = jnp.where(sidx == 0, xs[m_in], buf)
            # every stage processes its resident microbatch
            y = stage_fn(p, buf)
            # last stage retires microbatch t - (n_stages - 1)
            m_out = t - (n_stages - 1)
            live = (sidx == n_stages - 1) & (m_out >= 0)

            def write(o):
                return jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(m_out, 0), 0)

            outs = jnp.where(live, write(outs), outs)
            # hop activations to the next stage (ring; wraparound value
            # lands in stage 0's buffer and is overwritten next tick)
            buf = jax.lax.ppermute(y, axis, fwd)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # all-reduce so every stage row returns the retired outputs
        return jax.lax.psum(outs, axis)

    spec_p = jax.tree.map(lambda _: P(axis), stage_params)
    return shard_map(
        per_stage, mesh=mesh,
        in_specs=(spec_p, P()),
        out_specs=P(),
    )(stage_params, x_micro)


def split_stages(stacked_params, n_stages: int):
    """(L, ...) scan-stacked params -> (n_stages, L/n_stages, ...)."""
    def re(t):
        L = t.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return t.reshape(n_stages, L // n_stages, *t.shape[1:])
    return jax.tree.map(re, stacked_params)


def make_stage_fn(layer_fn):
    """Lift a per-layer ``layer_fn(layer_params, x) -> x`` into a stage_fn
    that scans its (L/n_stages)-deep slice."""
    def stage_fn(stage_params, x):
        def body(h, lp):
            return layer_fn(lp, h), None
        y, _ = jax.lax.scan(body, x, stage_params)
        return y
    return stage_fn


@functools.partial(jax.jit, static_argnames=("stage_fn", "mesh", "axis"))
def _jit_pipeline(stage_params, x_micro, *, stage_fn, mesh, axis):
    return pipeline_apply(stage_params, x_micro, stage_fn=stage_fn,
                          mesh=mesh, axis=axis)
