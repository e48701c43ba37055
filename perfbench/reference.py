"""What every architecture's reference shares, and the comparisons that
decide a run's ``correct``.

Each ``arch/<model_type>.py`` holds a plain float32 reference of its block
(``Reference``); they import nothing of the program and run every product
at ``Precision.HIGHEST``, a few rows at a time (``ROWS``).  Their control
(``fp8=True``) rounds every base linear's input and weight with
``fp8_round``.  The comparisons read what a reference and the program
give: the gap of each served token below the reference's best logit, and
the fine-tune's adapter leaves by the worst leaf.
"""
from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
ROWS = 4          # rows per block of every pass
QUIET = 1e-3      # a leaf whose gradient is under this share of the median
                  # leaf's is left out of the training comparison


def fp8_round(x: jax.Array, axis: int) -> jax.Array:
    """``x`` rounded to float8 e4m3 with one scale per slice along ``axis``,
    straight-through: gradients flow as if it were not there."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


@jax.jit
def embed(emb, tokens):
    return emb[tokens].astype(F32)


@jax.jit
def add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def leaves(tree, targets: Sequence[str]) -> Dict[str, np.ndarray]:
    """``{"<target>.<a|b>": float32 array}`` of an adapter tree, for the
    configuration's LoRA ``targets``."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        if len(keys) >= 2 and keys[-2] in targets:
            out[".".join(keys[-2:])] = np.asarray(x, np.float32)
    return out


def leaf_gap(got: Dict[str, np.ndarray], want: Dict[str, np.ndarray],
             keep: Sequence[str], diff: bool = False) -> float:
    """The worst leaf's gap between the norms of ``got`` and ``want`` (with
    ``diff``, the norm of their difference), over the larger of that leaf's
    norm in ``want`` and the median leaf's."""
    norm = {k: float(np.linalg.norm(want[k])) for k in keep}
    floor = float(np.median(list(norm.values())))
    worst = 0.0
    for k in keep:
        gap = (float(np.linalg.norm(got[k] - want[k])) if diff else
               abs(float(np.linalg.norm(got[k])) - norm[k]))
        worst = max(worst, gap / max(norm[k], floor, 1e-30))
    return worst


def moving_leaves(grad: Dict[str, np.ndarray]):
    """The leaves whose gradient norm is at least ``QUIET`` of the median
    leaf's: the others move under Adam by round-off alone."""
    norm = {k: float(np.linalg.norm(v)) for k, v in grad.items()}
    floor = QUIET * float(np.median(list(norm.values())))
    return sorted(k for k, n in norm.items() if n >= floor)


def token_gaps(ref_logits: Sequence[np.ndarray],
               chosen: Sequence[Sequence[int]]) -> np.ndarray:
    """How far below the reference's best logit each chosen token's
    reference logit lies (0 where the reference agrees)."""
    gaps = [lg.max(-1) - lg[np.arange(len(c)), np.asarray(c)]
            for lg, c in zip(ref_logits, chosen)]
    return np.concatenate(gaps) if gaps else np.zeros((0,))
