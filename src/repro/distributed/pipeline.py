"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

For depth-dominated models at >512 chips, a third parallelism axis becomes
necessary (DP x TP saturates).  This wrapper maps *stages* onto an existing
mesh axis: stage s holds layers [s*L/S, (s+1)*L/S); microbatches stream
through a ``collective_permute`` ring, so at steady state every stage
computes a different microbatch (classic GPipe fill/drain bubble of
(S-1)/(M+S-1)).

Expressed as shard_map + lax.fori_loop + ppermute — the jax-native
translation of the send/recv pipelines of Megatron/DeepSpeed.  Stages whose
slot is empty during fill/drain compute masked work (the standard SPMD
formulation; the bubble is wall-clock, not correctness).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map


def pipeline_apply(fn: Callable, stage_params, x_micro, *, mesh: Mesh,
                   stage_axis: str):
    """Run ``fn(params_s, x)`` through S pipeline stages.

    fn:           shape-preserving stage function (e.g. a block of layers)
    stage_params: pytree with leading dim S, sharded P(stage_axis) — stage
                  s's parameters live on stage s's shard
    x_micro:      [M, mb, ...] microbatched input (replicated)
    returns       [M, mb, ...] outputs (replicated)
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    S = sizes[stage_axis]
    M = x_micro.shape[0]
    T = M + S - 1
    perm = [(i, (i + 1) % S) for i in range(S)]

    def body(params_local, xs):
        p = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(stage_axis)
        cur = jnp.zeros_like(xs[0])
        out = jnp.zeros_like(xs)

        def step(t, carry):
            cur, out = carry
            # receive the previous stage's last output (ring permute)
            recv = jax.lax.ppermute(cur, stage_axis, perm)
            m_in = jnp.clip(t, 0, M - 1)
            inp = jnp.where(sid == 0, xs[m_in], recv)
            active = (t >= sid) & (t - sid < M)
            y = fn(p, inp)
            cur = jnp.where(active, y, cur)
            # the last stage emits microbatch (t - sid)
            m_out = jnp.clip(t - sid, 0, M - 1)
            write = active & (sid == S - 1)
            out = out.at[m_out].set(jnp.where(write, y, out[m_out]))
            return cur, out

        _, out = jax.lax.fori_loop(0, T, step, (cur, out))
        # only the last stage holds real outputs; replicate via psum
        out = out * (sid == S - 1)
        return jax.lax.psum(out, stage_axis)

    spec = jax.tree.map(lambda _: P(stage_axis), stage_params)
    return shard_map(
        body, mesh=mesh,
        in_specs=(spec, P()), out_specs=P(),
        check_vma=False,
    )(stage_params, x_micro)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    """GPipe fill/drain overhead: (S-1) / (M+S-1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
