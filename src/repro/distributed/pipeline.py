"""Pipeline parallelism (GPipe-style) over a ``stage`` mesh axis.

Completes the parallelism matrix (DP/TP/EP/SP are GSPMD-native in this
framework; PP needs explicit scheduling).  The design is the TPU-idiomatic
one: layers are split into S contiguous stages, each stage's parameters
live on one ``stage`` mesh slice, and microbatches stream through a
shard_map whose inner loop moves activations between neighbouring stages
with ``jax.lax.ppermute`` (ICI neighbour hops — the cheapest collective on
a torus).

Schedule: the classic GPipe loop runs ``n_micro + S - 1`` ticks; at tick t
stage s processes microbatch ``t - s`` (bubble fraction (S-1)/(n_micro+S-1)).
Every device executes the same program (SPMD): idle ticks compute on junk
and mask the result, which costs bubble-flops but no control flow — the
standard trade on systolic hardware.

This module is deliberately model-agnostic: ``stage_fn(stage_params, x)``
is any per-stage function (e.g. a scan over that stage's layer slice).  The
training integration point is ``make_pipelined_apply`` whose output
composes with jax.grad — ppermute is differentiable, so the backward pass
is the reverse pipeline automatically.

Validated on an 8-device host mesh in tests/test_pipeline.py: exactness vs
the unpipelined reference, gradient equality, and bubble accounting.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stage_split(n_layers: int, n_stages: int) -> list:
    """Contiguous layer ranges per stage (LPT is unnecessary: uniform
    layers; uneven remainders go to the later stages so stage 0 — which
    also holds the embedding in typical use — is lightest)."""
    base = n_layers // n_stages
    extra = n_layers % n_stages
    out = []
    lo = 0
    for s in range(n_stages):
        hi = lo + base + (1 if s >= n_stages - extra else 0)
        out.append((lo, hi))
        lo = hi
    return out


def make_pipelined_apply(stage_fn: Callable, mesh: Mesh, *,
                         stage_axis: str = "stage",
                         n_micro: int | None = None):
    """Build ``apply(stage_params, x) -> y`` running ``stage_fn`` as a
    GPipe pipeline over ``stage_axis``.

    ``stage_params``: pytree with a leading stage axis on every leaf
    (sharded P(stage_axis, ...)).  ``x``: (n_micro, mb, ...) microbatched
    input, replicated across the stage axis.  Returns y with the same
    leading (n_micro, mb) layout.
    """
    S = mesh.shape[stage_axis]

    def apply(stage_params, x):
        nm = x.shape[0] if n_micro is None else n_micro
        assert x.shape[0] == nm

        def per_stage(params, xs):
            # params: this stage's slice (leading stage dim of size 1)
            params = jax.tree.map(lambda p: p[0], params)
            sidx = jax.lax.axis_index(stage_axis)
            T = nm + S - 1
            mb_shape = xs.shape[1:]

            def tick(t, carry):
                inflight, outputs = carry
                # stage 0 ingests microbatch t (or junk when t >= nm)
                mb_in = jax.lax.dynamic_index_in_dim(
                    xs, jnp.minimum(t, nm - 1), 0, keepdims=False)
                z = jnp.where(sidx == 0, mb_in, inflight)
                z = stage_fn(params, z, sidx)
                # last stage emits microbatch t - (S - 1)
                out_idx = jnp.clip(t - (S - 1), 0, nm - 1)
                emit = (sidx == S - 1) & (t >= S - 1)
                outputs = jax.lax.cond(
                    emit,
                    lambda o: jax.lax.dynamic_update_index_in_dim(
                        o, z, out_idx, 0),
                    lambda o: o, outputs)
                # shift: stage s -> s+1 (ring permute; the wrap edge is
                # overwritten by stage 0's ingest next tick)
                nxt = jax.lax.ppermute(
                    z, stage_axis,
                    [(i, (i + 1) % S) for i in range(S)])
                return nxt, outputs

            inflight0 = jnp.zeros(mb_shape, xs.dtype)
            outputs0 = jnp.zeros((nm,) + mb_shape, xs.dtype)
            _, outputs = jax.lax.fori_loop(
                0, T, tick, (inflight0, outputs0))
            # only the last stage holds real outputs; broadcast them back
            # so every stage replica returns the same value (out_specs
            # replicate over the stage axis).
            outputs = jax.lax.psum(
                jnp.where(sidx == S - 1, outputs, 0.0), stage_axis)
            return outputs

        pspecs = jax.tree.map(lambda _: P(stage_axis), stage_params)
        return shard_map(per_stage, mesh=mesh,
                         in_specs=(pspecs, P()), out_specs=P(),
                         check_vma=False)(stage_params, x)

    return apply


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    """GPipe bubble: (S-1) / (n_micro + S - 1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
