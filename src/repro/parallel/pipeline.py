"""Pipeline parallelism across the pod axis (paper §V-B, generalized).

The paper scales inference with up-to-4-way pipeline parallelism over a
ring of ICI links.  Here: layers are split into ``P`` stages along a mesh
axis; microbatches stream GPipe-style through the ring with
``jax.lax.ppermute`` hops inside ``shard_map``.  Steady-state throughput
is one microbatch per stage-time; the (P-1)-step fill/drain bubble is
amortized by the microbatch count — the same analytical model
repro.core.multichip uses, now as executable JAX.

``pipeline_apply`` is deliberately model-agnostic: ``stage_fn(params, x)
-> x`` applies one stage's layers; stage params are pre-stacked with a
leading stage axis and sharded onto the pipeline mesh axis.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe_loop(stage_fn: Callable, stage_params, micro_x: jax.Array,
               axis_name: str, n_stages: int | None = None) -> jax.Array:
    """Runs inside shard_map.  micro_x: [M, mb, ...] (valid on stage 0);
    stage_params: this stage's parameter tree.  Returns [M, mb, ...]
    outputs (valid on the last stage).  ``n_stages`` is the static
    pipeline depth (mesh axis size); older jax has no
    ``jax.lax.axis_size`` to recover it inside shard_map."""
    if n_stages is None:
        n_stages = jax.lax.axis_size(axis_name)
    stage = jax.lax.axis_index(axis_name)
    M = micro_x.shape[0]
    T = M + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    outs0 = jnp.zeros_like(micro_x)
    recv0 = jnp.zeros_like(micro_x[0])

    def body(carry, t):
        recv, outs = carry
        # stage 0 injects microbatch t; others consume the received buffer
        inj = micro_x[jnp.clip(t, 0, M - 1)]
        x_in = jnp.where(stage == 0, inj, recv)
        active = (t - stage >= 0) & (t - stage < M)
        y = stage_fn(stage_params, x_in)
        y = jnp.where(active, y, x_in)
        # last stage records microbatch (t - (P-1)) when valid
        out_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
        take = active & (stage == n_stages - 1)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(take, y, outs[out_idx]), out_idx, 0)
        # hand off to the next stage over the ring
        recv = jax.lax.ppermute(y, axis_name, perm)
        return (recv, outs), None

    (_, outs), _ = jax.lax.scan(body, (recv0, outs0), jnp.arange(T))
    # only the last stage holds real outputs (others are zero) — psum
    # replicates them ring-wide so out_specs=P() is well-defined
    return jax.lax.psum(outs, axis_name)


def pipeline_apply(mesh: Mesh, axis_name: str, stage_fn: Callable,
                   stacked_params, x: jax.Array, microbatches: int):
    """x: [B, ...] -> [B, ...] through ``P = mesh.shape[axis_name]`` stages.

    ``stacked_params``: tree with leading stage axis (sharded over
    ``axis_name``); non-pipeline mesh axes pass through for in-stage
    DP/TP.
    """
    B = x.shape[0]
    assert B % microbatches == 0
    micro = x.reshape(microbatches, B // microbatches, *x.shape[1:])

    param_specs = jax.tree.map(lambda _: P(axis_name), stacked_params)
    fn = jax.shard_map(
        lambda p, mx: gpipe_loop(
            lambda pp, xx: stage_fn(jax.tree.map(lambda a: a[0], pp), xx),
            p, mx, axis_name, n_stages=mesh.shape[axis_name]),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn(stacked_params, micro)
    return out.reshape(B, *out.shape[2:])
