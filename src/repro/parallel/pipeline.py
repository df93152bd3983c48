"""GPipe-style pipeline parallelism over ``shard_map`` + ``ppermute``.

An alternative to FSDP for the slow ``pod`` axis: stages hold disjoint layer
ranges; microbatches stream through with collective-permutes between stages.
The classic schedule executes ``n_micro + n_stages - 1`` ticks; bubble
fraction = (S-1)/(M+S-1).

This is a *library* component (tested at small scale in
tests/test_pipeline.py); the dry-run default uses FSDP over ``pod`` because
the roofline favors it at 2 pods, but at deeper pod counts the launcher can
select ``pipeline_stage_fn`` instead — see DESIGN.md §6.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_fn(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                n_stages: int, n_micro: int, axis: str = "pipe"):
    """Build a pipelined apply: (stage_params, x_microbatched) -> y.

    ``stage_fn(params_for_stage, x)`` runs one stage on one microbatch.
    Inside shard_map over ``axis``: each device holds one stage's params;
    microbatches rotate through via ppermute.

    x_microbatched: (n_micro, mb, ...) sharded P(None) per stage (replicated
    entry; stage 0 consumes, others ignore until their tick).
    """

    def pipelined(stage_params, x_micro):
        idx = jax.lax.axis_index(axis)
        mb_shape = x_micro.shape[1:]
        n_ticks = n_micro + n_stages - 1

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (if in range)
            take = jnp.clip(t, 0, n_micro - 1)
            fresh = jax.lax.dynamic_index_in_dim(x_micro, take, 0,
                                                 keepdims=False)
            inp = jnp.where(idx == 0,
                            jnp.where(t < n_micro, fresh,
                                      jnp.zeros_like(fresh)),
                            state)
            out = stage_fn(stage_params, inp)
            # pass stage s -> s+1
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            passed = jax.lax.ppermute(out, axis, perm)
            # last stage emits at tick t for microbatch t - (S-1)
            emit_slot = t - (n_stages - 1)
            outputs = jnp.where(
                (idx == n_stages - 1) & (emit_slot >= 0),
                jax.lax.dynamic_update_index_in_dim(
                    outputs, out, jnp.clip(emit_slot, 0, n_micro - 1), 0),
                outputs)
            return (passed, outputs), None

        outputs0 = jnp.zeros((n_micro,) + mb_shape, x_micro.dtype)
        state0 = jnp.zeros(mb_shape, x_micro.dtype)
        (_, outputs), _ = jax.lax.scan(
            tick, (state0, outputs0), jnp.arange(n_ticks))
        # gather final outputs from the last stage to all
        outputs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outputs, jnp.zeros_like(outputs)),
            axis)
        return outputs

    return pipelined


def run_pipeline(mesh: Mesh, stage_fn, stage_params_stacked, x_micro,
                 n_micro: int, axis: str = "pipe"):
    """Convenience wrapper: shard_map the pipelined fn over ``axis``.

    stage_params_stacked: pytree with leading dim == n_stages on EVERY leaf.
    x_micro: (n_micro, mb, ...) input microbatches.

    The leading stage dim is load-bearing twice over: leaves are sharded
    ``P(axis)`` on dim 0 (one stage's slice per device) and the shard_map
    body slices ``leaf[0]`` to unwrap it.  A leaf without that dim used to
    be silently mis-sliced (its *first row* became every stage's "params")
    or rejected by the partitioner with an opaque divisibility error, so
    the shapes are validated up front and a mismatch names the leaf.
    """
    n_stages = mesh.shape[axis]
    for kp, leaf in jax.tree_util.tree_flatten_with_path(
            stage_params_stacked)[0]:
        shape = tuple(getattr(leaf, "shape", ()))
        if not shape or shape[0] != n_stages:
            name = jax.tree_util.keystr(kp) or "<root>"
            raise ValueError(
                f"run_pipeline: params leaf {name} has shape {shape}; every "
                f"leaf needs a leading stage dimension of size n_stages == "
                f"{n_stages} (mesh axis {axis!r}) — stack per-stage params "
                f"with jax.tree.map(lambda *xs: jnp.stack(xs), *stages)")
    fn = pipeline_fn(stage_fn, n_stages, n_micro, axis)
    in_specs = (
        jax.tree.map(lambda _: P(axis), stage_params_stacked),
        P(),
    )
    body = lambda sp, x: fn(jax.tree.map(lambda a: a[0], sp), x)
    mapped = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                           out_specs=P(), check_vma=False)
    return mapped(stage_params_stacked, x_micro)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
