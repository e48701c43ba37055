"""Multi-LoRA adapter algebra.

A *LoRA bank* holds ``n_slots`` adapters stacked on a leading axis so that a
single kernel call serves every token in a mixed-adapter token stream (the
paper's SMLM design).  Per-token adapter ids select the adapter; id ``-1``
(or any out-of-range id) means "base model only" and contributes nothing.

Layout per target linear (stacked over scan periods where applicable):
    a: [..., n_slots, d_in, r]     (gaussian init — matches the paper's
                                    ``init_lora_weights=gaussian``)
    b: [..., n_slots, r, d_out]    (zeros init — standard LoRA)

Static scaling (alpha/r) is folded into ``b`` at materialisation, exactly as
the paper folds it into the weight tensor at ``MixedLoraModel`` instantiation;
dynamic per-request scaling is applied via the ``scale_t`` per-token vector.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class LoRAConfig:
    n_slots: int = 4            # resident adapter slots
    r: int = 8
    alpha: float = 16.0
    dropout: float = 0.05       # used by the trainer (train-time only)
    # which linears receive adapters; mirrors the paper's "Full" setting
    # (q,k,v,o,up,gate,down).  Schema marks eligible leaves; this filters.
    targets: Tuple[str, ...] = ("wq", "wk", "wv", "wo", "wg", "wu", "wd",
                                "wdkv", "in_x", "in_z", "out_proj")

    @property
    def scaling(self) -> float:
        return self.alpha / self.r


def lora_apply_ref(x: jax.Array, a: jax.Array, b: jax.Array,
                   ids: jax.Array, scale_t: Optional[jax.Array] = None
                   ) -> jax.Array:
    """Pure-jnp oracle for SMLM: one-hot mixed multi-LoRA matmul.

    x: [T, d_in]; a: [n, d_in, r]; b: [n, r, d_out]; ids: [T] int32.
    Out-of-range ids produce an all-zero one-hot row -> no adapter.
    """
    n = a.shape[0]
    onehot = jax.nn.one_hot(ids, n, dtype=x.dtype)            # [T, n]
    if scale_t is not None:
        onehot = onehot * scale_t[:, None].astype(x.dtype)
    xa = jnp.einsum("td,ndr->tnr", x, a.astype(x.dtype))       # [T, n, r]
    xa = xa * onehot[:, :, None]
    return jnp.einsum("tnr,nro->to", xa, b.astype(x.dtype))


def lora_apply(x: jax.Array, a: jax.Array, b: jax.Array, ids: jax.Array,
               scale_t: Optional[jax.Array] = None,
               impl: str = "ref") -> jax.Array:
    """Multi-LoRA matmul.  ``ref`` (every served and trained path, on every
    backend) is the differentiable jnp one-hot form.  ``smlm`` runs the
    compiled Pallas SMLM kernel and ``interpret`` the same kernel in
    interpret mode; both need adapter-uniform ``block_t`` tiles and have no
    VJP, so only kernel tests call them."""
    if impl == "ref":
        return lora_apply_ref(x, a, b, ids, scale_t)
    from repro.kernels import ops as kops
    return kops.smlm(x, a, b, ids, scale_t, interpret=(impl == "interpret"))


def dense(x: jax.Array, w: jax.Array, bias: Optional[jax.Array],
          lora: Optional[dict], ids: Optional[jax.Array],
          scale_t: Optional[jax.Array] = None, impl: str = "ref"
          ) -> jax.Array:
    """Joint base + multi-LoRA linear over a flattened token stream [T, d].

    This is the paper's unified projection: ONE base matmul for every request
    type plus ONE segmented multi-LoRA multiplication, instead of a per-adapter
    loop (cf. Section 3.3).
    """
    y = x @ w.astype(x.dtype)
    if bias is not None:
        y = y + bias.astype(x.dtype)
    if lora is not None and ids is not None:
        y = y + lora_apply(x, lora["a"], lora["b"], ids, scale_t, impl=impl)
    return y


def init_lora_bank(key: jax.Array, schema_targets, lcfg: LoRAConfig,
                   dtype=jnp.float32, gaussian_b: bool = False):
    """Materialise a LoRA bank for ``schema_targets``: a pytree whose leaves
    are ``repro.models.schema.LoraTarget`` descriptors.  Returns a parallel
    pytree of {"a": ..., "b": ...}.  ``b`` is zeros by default (standard LoRA
    init); ``gaussian_b`` matches the paper's fine-tuning-experiment setting
    of fully gaussian adapters.  Static alpha/r scaling is folded into ``b``
    at materialisation (the paper folds it into the weight at instantiation).
    """
    from repro.models.schema import LoraTarget
    is_leaf = lambda x: isinstance(x, LoraTarget)
    leaves, treedef = jax.tree_util.tree_flatten(schema_targets, is_leaf=is_leaf)
    keys = jax.random.split(key, max(2 * len(leaves), 2))
    out = []
    for i, tgt in enumerate(leaves):
        a_shape = (*tgt.stack, lcfg.n_slots, tgt.d_in, lcfg.r)
        b_shape = (*tgt.stack, lcfg.n_slots, lcfg.r, tgt.d_out)
        a = jax.random.normal(keys[2 * i], a_shape, dtype) \
            * (1.0 / jnp.sqrt(tgt.d_in))
        if gaussian_b:
            b = jax.random.normal(keys[2 * i + 1], b_shape, dtype) \
                * (0.02 * lcfg.scaling)
        else:
            b = jnp.zeros(b_shape, dtype)
        out.append({"a": a, "b": b})
    return jax.tree_util.tree_unflatten(treedef, out)


def abstract_lora_bank(schema_targets, lcfg: LoRAConfig, dtype=jnp.float32):
    """ShapeDtypeStruct mirror of ``init_lora_bank`` (for the dry-run)."""
    from repro.models.schema import LoraTarget
    is_leaf = lambda x: isinstance(x, LoraTarget)
    return jax.tree_util.tree_map(
        lambda t: {"a": jax.ShapeDtypeStruct((*t.stack, lcfg.n_slots, t.d_in, lcfg.r), dtype),
                   "b": jax.ShapeDtypeStruct((*t.stack, lcfg.n_slots, lcfg.r, t.d_out), dtype)},
        schema_targets, is_leaf=is_leaf)


def merge_adapter(w: jax.Array, a: jax.Array, b: jax.Array,
                  slot: int) -> jax.Array:
    """Merge one adapter slot into the base weight (the *static_merge*
    baseline; destroys multi-adapter flexibility — cf. DESIGN.md)."""
    return w + a[slot] @ b[slot]


def slot_token_ids(row_adapter: jax.Array, row_len: int) -> jax.Array:
    """Expand per-row adapter ids to per-token ids for a [B, S] bucket."""
    return jnp.repeat(row_adapter, row_len)
