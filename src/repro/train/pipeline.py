"""Pipeline parallelism over a mesh axis via LISA hop transfers (GPipe).

Stage-to-stage activation movement is a planned movement: a stage->stage
``movement.Transfer`` lowers to a single neighbor hop-chain leg (the
``ppermute`` shift = the RBM primitive, executed by the ``hop_chain``
backend), exactly the paper's adjacent-subarray path: stage s computes a
microbatch, its output hops one link to stage s+1 while stage s starts the
next microbatch — the classic GPipe schedule with n_stages + n_micro - 1
slots.  The plan's ``MovementCost`` prices each hop with the ICI analogue
of Table 1's linear model.

Implementation: `shard_map` over the pipeline axis; every device holds its
stage's parameters (stacked layer group), the schedule runs a fori_loop over
slots with a rotating microbatch buffer.  Used for the optional PP config
(DESIGN.md §3) and exercised by tests/test_pipeline.py on 4 host devices;
on the production mesh the natural pipeline axis is "pod".
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import movement as MV


def gpipe(stage_fn: Callable, axis_name: str):
    """Build a pipelined forward: ``stage_fn(params_stage, x) -> y``.

    Returns ``run(params_stacked, micro_in) -> micro_out`` to be called
    INSIDE shard_map over ``axis_name``:
      params_stacked: this device's stage params (leading stage dim removed
                      by shard_map's in_spec).
      micro_in: (n_micro, mb, ...) microbatches, replicated; microbatch m
                enters stage 0 at slot m, exits stage S-1 at slot m + S - 1.
    """

    def run(stage_params, micro_in):
        n_stages = jax.lax.axis_size(axis_name)
        idx = jax.lax.axis_index(axis_name)
        n_micro = micro_in.shape[0]
        n_slots = n_stages + n_micro - 1

        micro_in = jax.lax.pcast(micro_in, (axis_name,), to="varying")
        out_shape = jax.eval_shape(stage_fn, stage_params, micro_in[0])
        # Stage-to-stage hop as a movement plan: one neighbor-shift
        # hop-chain leg, planned once per activation shape at trace time.
        hop_plan = MV.plan(MV.Transfer(
            MV.Tier("stage", axis=axis_name), MV.Tier("stage", axis=axis_name),
            MV.Layout.dense(out_shape.shape, out_shape.dtype)))
        outputs = jnp.zeros((n_micro,) + out_shape.shape, out_shape.dtype)
        outputs = jax.lax.pcast(outputs, (axis_name,), to="varying")
        carry_in = jnp.zeros_like(micro_in[0])

        def slot(t, state):
            carry_in, outputs = state
            m = t - idx                       # microbatch index at this stage
            active = (m >= 0) & (m < n_micro)
            x = jnp.where(idx == 0,
                          micro_in[jnp.clip(m, 0, n_micro - 1)], carry_in)
            y = stage_fn(stage_params, x)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # RBM hop: activations move one link toward the next stage
            carry_next = MV.execute(hop_plan, data=y)["data"]
            done = active & (idx == n_stages - 1)
            outputs = jax.lax.cond(
                done,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(m, 0, n_micro - 1), 0),
                lambda o: o, outputs)
            return carry_next, outputs

        _, outputs = jax.lax.fori_loop(0, n_slots, slot,
                                       (carry_in, outputs))
        # results live on the last stage; hop them back to stage 0 owners
        # (one wraparound link) so every stage returns the same outputs
        return jax.lax.psum(outputs, axis_name)

    return run


def pipeline_transformer(mesh: Mesh, axis_name: str, layer_fn: Callable,
                         n_layers_per_stage: int):
    """Convenience: stage = scan over this stage's layer slice."""

    def stage_fn(stage_params, x):
        def body(h, p):
            return layer_fn(p, h), None
        out, _ = jax.lax.scan(body, x, stage_params)
        return out

    run = gpipe(stage_fn, axis_name)

    def pipelined(params_stacked, micro_in):
        # params_stacked: (n_stages, n_layers_per_stage, ...) pytree;
        # shard_map keeps the (length-1) stage dim — squeeze it per device.
        def body(p, m):
            return run(jax.tree.map(lambda a: a[0], p), m)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis_name), P()),
            out_specs=P())(params_stacked, micro_in)

    return pipelined
