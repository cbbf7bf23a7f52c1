"""Pipeline parallelism — GPipe-style microbatch schedule over a mesh axis.

Reference: **ABSENT in the reference** (SURVEY.md §2.6 — DL4J has no
pipeline parallelism).  This is a NEW capability of the TPU build, designed
the TPU-native way:

- the pipeline's S stages must be STRUCTURALLY UNIFORM blocks (the
  transformer regime: S identical layer-stacks).  Stage params are stacked
  on a leading (S, ...) axis and sharded over the mesh's ``stage`` axis, so
  each device group holds one stage's weights;
- the schedule is a ``lax.scan`` over S + M - 1 ticks inside ``shard_map``:
  each tick every stage processes one microbatch slot and hands its
  activation to the next stage with a single-hop ``lax.ppermute`` (ICI
  neighbour exchange) — compute and communication overlap tick-to-tick;
- the whole schedule (all ticks, all stages) is ONE jitted XLA executable,
  and it is differentiable: ``jax.grad`` through scan + ppermute yields the
  reverse schedule automatically (backward bubbles included).

Use :class:`PipelineStack` for the common case; ``pipeline_apply`` is the
functional core.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["PipelineStack", "pipeline_apply"]


def _varying(x, axis_name):
    """Mark ``x`` varying over ``axis_name`` for shard_map's vma type
    system, which checks scan carries at the loop boundary."""
    return lax.pcast(x, axis_name, to="varying")


def pipeline_apply(mesh, block_fn: Callable, stacked_params, x,
                   n_microbatches: int, axis_name: str = "stage"):
    """Run ``block_fn(params_s, h) -> h`` through S pipelined stages.

    ``stacked_params``: pytree with leading stage axis S (sharded over
    ``axis_name``); ``x``: (batch, ...) global input, batch divisible by
    ``n_microbatches``.  Returns the pipeline output (batch, ...).
    """
    jmesh = getattr(mesh, "mesh", mesh)
    S = jmesh.shape[axis_name]
    M = n_microbatches
    # batch dim shards over the mesh's data axis (if present) so the
    # declared data parallelism does real work; each data shard runs its
    # own microbatch schedule
    D = jmesh.shape.get("data", 1)
    data_axis = "data" if D > 1 else None
    if x.shape[0] % (M * D):
        raise ValueError(f"batch {x.shape[0]} not divisible by "
                         f"microbatches*data = {M}*{D}")

    def per_stage(params_local, x_local):
        # params_local: (1, ...) this stage's slice; x_local: full batch
        # (replicated input — stage 0 consumes it, later stages ignore it)
        p = jax.tree.map(lambda a: a[0], params_local)
        sid = lax.axis_index(axis_name)
        mb = x_local.reshape(M, x_local.shape[0] // M, *x_local.shape[1:])
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        # initial carries must already be marked stage-varying: the scan
        # body makes them varying (axis_index/ppermute), and scan requires
        # carry-in and carry-out types to match
        state = _varying(jnp.zeros_like(mb[0]), axis_name)
        outs = _varying(jnp.zeros_like(mb), axis_name)

        def tick(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t (older stages work on in-flight)
            inject = jnp.where(t < M, t, 0)
            state = jnp.where(sid == 0,
                              jnp.where(t < M, mb[inject], state * 0),
                              state)
            h = block_fn(p, state)
            # last stage banks finished microbatch (t - (S-1))
            done_idx = t - (S - 1)
            bank = jnp.logical_and(sid == S - 1,
                                   jnp.logical_and(done_idx >= 0,
                                                   done_idx < M))
            outs = jnp.where(
                bank,
                lax.dynamic_update_index_in_dim(
                    outs, h, jnp.clip(done_idx, 0, M - 1), 0),
                outs)
            # hand activation downstream (ring hop; stage S-1 -> 0 is junk
            # that stage 0 overwrites on inject)
            state = lax.ppermute(h, axis_name, fwd_perm)
            return (state, outs), None

        (_, outs), _ = lax.scan(tick, (state, outs),
                                jnp.arange(S + M - 1))
        # only stage S-1 holds real outputs: broadcast them to all stages
        outs = lax.psum(jnp.where(sid == S - 1, outs, jnp.zeros_like(outs)),
                        axis_name)
        return outs.reshape(x_local.shape)

    pspec = jax.tree.map(lambda _: P(axis_name), stacked_params)
    xspec = P(data_axis) if data_axis else P()
    fn = jax.shard_map(per_stage, mesh=jmesh,
                       in_specs=(pspec, xspec), out_specs=xspec)
    return fn(stacked_params, x)


class PipelineStack:
    """S uniform blocks trained as a pipeline.

    ``init_block(key) -> params`` builds ONE block's params;
    ``block_fn(params, h) -> h`` applies it.  ``PipelineStack`` stacks S
    copies, shards them over the mesh's stage axis, and exposes a jitted
    pipelined ``apply`` / ``grad``-able loss hook.
    """

    def __init__(self, mesh, init_block: Callable, block_fn: Callable,
                 n_stages: Optional[int] = None, n_microbatches: int = 4,
                 axis_name: str = "stage", seed: int = 0):
        self.mesh = mesh
        jmesh = getattr(mesh, "mesh", mesh)
        self.axis_name = axis_name
        self.S = n_stages or jmesh.shape[axis_name]
        if self.S != jmesh.shape[axis_name]:
            raise ValueError(f"n_stages {self.S} != mesh axis "
                             f"{jmesh.shape[axis_name]}")
        self.M = n_microbatches
        self.block_fn = block_fn
        keys = jax.random.split(jax.random.PRNGKey(seed), self.S)
        per_stage = [init_block(k) for k in keys]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage)
        pspec = jax.tree.map(lambda _: P(axis_name), stacked)
        self.params = jax.device_put(
            stacked, jax.tree.map(
                lambda s: NamedSharding(jmesh, s), pspec))

    def apply(self, params, x):
        return pipeline_apply(self.mesh, self.block_fn, params, x,
                              self.M, self.axis_name)

    def __call__(self, x):
        return self.apply(self.params, x)
