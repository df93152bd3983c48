"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout the engine is handed (``repro.models`` declares
it); the values are the benchmark's own.  Matrices are normal with
variance 1/fan-in, so activations and logits stay of order one at any
depth; the embedding has variance 1/d, which the engine's ``sqrt(d)``
scaling brings to one.  Norm gains and biases are drawn too, so that a
path which drops them does not go unseen.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["make_params", "seed_key"]

_GAIN_STD = 0.1
_BIAS_STD = 0.5


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (wider than 32 bits
    too): the low and high words are folded in apart."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _std(path: str, shape, d: int) -> float:
    leaf = path.rsplit("/", 1)[-1]
    if leaf == "embed":
        return d ** -0.5
    if "norm" in leaf:
        return _GAIN_STD
    if leaf in ("bq", "bk", "bv"):
        return _BIAS_STD
    return shape[-2] ** -0.5


def make_params(abstract_tree, d: int, seed: int):
    """Concrete weights for ``abstract_tree`` (shape/dtype leaves)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    specs = [(leaf.shape, leaf.dtype) for _, leaf in flat]

    @jax.jit
    def build(key):
        out = []
        for i, (path, (shape, dtype)) in enumerate(zip(paths, specs)):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out.append((x * _std(path, shape, d)).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return build(seed_key(seed))
