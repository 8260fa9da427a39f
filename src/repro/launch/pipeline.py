"""GPipe-style pipeline parallelism over a mesh axis (DESIGN.md §6 "PP").

The multi-pod mesh's ``pod`` axis can act as a pipeline instead of pure DP:
layers are split into ``n_stages`` contiguous groups, stage s's parameters
live on pod s, and microbatches rotate through stages via
``collective_permute`` — the canonical SPMD GPipe schedule:

  step t ∈ [0, n_micro + n_stages − 1):
    every stage runs its layer group on the activation it holds (masked out
    during its fill/drain bubbles), then passes the result to stage s+1.

Generic over a ``stage_fn(stage_params, x)``; correctness is checked against
the sequential composition in the multi-device selftest.

Cost model: bubble fraction = (S−1)/(T+S−1); wire = activation bytes per
microbatch per hop, visible to the roofline parser as collective-permutes.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(params, n_stages: int):
    """Reshape scan-stacked (L, …) leaves to (n_stages, L/n_stages, …)."""

    def one(a):
        L = a.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return a.reshape((n_stages, L // n_stages) + a.shape[1:])

    return jax.tree.map(one, params)


def gpipe(
    mesh: Mesh,
    axis: str,
    stage_fn: Callable,
    n_micro: int,
):
    """Build ``run(stage_params, x_micro) -> y_micro`` (both global-view).

    ``stage_params``: leaves (n_stages, …), sharded over ``axis`` dim 0.
    ``x_micro``: (n_micro, B_m, …) replicated; returns same shape, the
    result of all stages applied in order.
    """
    n_stages = mesh.shape[axis]

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    def run(stage_params, x_micro):
        sp = jax.tree.map(lambda a: a[0], stage_params)  # this stage's params
        stage = jax.lax.axis_index(axis)
        T = n_micro + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        buf = jnp.zeros_like(x_micro[0])  # activation currently held here
        outs = jnp.zeros_like(x_micro)

        def step(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (while t < n_micro)
            take = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where((stage == 0) & (t < n_micro), x_micro[take], buf)
            active = ((t - stage) >= 0) & ((t - stage) < n_micro)
            y = stage_fn(sp, x_in)
            y = jnp.where(active, y, x_in)
            # last stage emits microbatch (t − stage) when active
            emit = jnp.clip(t - stage, 0, n_micro - 1)
            outs = jax.lax.cond(
                active & (stage == n_stages - 1),
                lambda o: o.at[emit].set(y),
                lambda o: o,
                outs,
            )
            # rotate activations one stage forward
            buf_next = jax.lax.ppermute(y, axis, fwd_perm)
            return (buf_next, outs), None

        (buf, outs), _ = jax.lax.scan(step, (buf, outs), jnp.arange(T))
        # outs is only valid on the last stage; replicate via masked psum
        outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    return run
