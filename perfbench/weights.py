"""Weights and adapters drawn from a run's seed, on the device, by one
jitted program each.

The generator takes only shapes (a pytree of ``jax.ShapeDtypeStruct``) and
the seed, so the reference can draw the same numbers again after the
program's state is freed: nothing it compares against comes from the
program.  Every leaf gets its own key (the seed's key folded with the
leaf's index), in the dtype the leaf is served in.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

def seed_key(seed: int, stream: int) -> jax.Array:
    """A raw threefry key from any non-negative whole number (seeds may not
    fit in 32 bits) and a stream number."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jnp.asarray(words, dtype=jnp.uint32)


def _name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def weight_std(norms: Sequence[str]) -> Callable:
    """Standard deviation of a base-model leaf; None for a leaf named in
    ``norms`` (ones).  The scale the program's own initializer uses: 0.02,
    or 1/sqrt(fan-in) where that is smaller."""
    def std(path, shape) -> Optional[float]:
        if _name(path) in norms:
            return None
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        return min(0.02, fan_in ** -0.5)
    return std


def adapter_std(b_scale: float) -> Callable:
    """LoRA factors: ``a`` ~ N(0, 1/d_in), ``b`` ~ N(0, b_scale^2), with
    alpha/r folded into ``b_scale`` (both factors random, so every adapter
    changes every target)."""
    def std(path, shape):
        return shape[-2] ** -0.5 if _name(path) == "a" else b_scale
    return std


def maker(shapes, std_fn: Callable) -> Callable:
    """One jitted program ``key -> pytree`` drawing every leaf of
    ``shapes``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    @jax.jit
    def make(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            std = std_fn(path, s.shape)
            if std is None:
                out.append(jnp.ones(s.shape, s.dtype))
                continue
            x = jax.random.normal(jax.random.fold_in(key, i), s.shape, s.dtype)
            out.append((x * jnp.asarray(std, s.dtype)).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return make
