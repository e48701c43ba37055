"""Logical-axis -> mesh sharding rules.

Strategies:
  "tp"      — tensor parallel over the "model" axis only; params replicated
              across data/pod.
  "fsdp_tp" — additionally shard the "embed" logical axis of every weight
              over "data" (FSDP); pods replicate (DP across pods).  This is
              the baseline for >=100B configs (they cannot fit replicated).

Divisibility is checked per leaf: a dimension that does not divide the mesh
axis is replicated (e.g. 40 attention heads or 8 KV heads on a 16-way model
axis, the 50280/51865 vocabs).  Head-count sharding is only applied when the
HEAD COUNT divides the axis — sharding the flattened h*hd dim across head
boundaries would force per-layer resharding after the reshape to heads.

SSM projections shard over "model" (head-parallel Mamba TP) because the
schema emits head-ALIGNED component projections (separate z/x/BC/dt weights)
instead of one fused zxBCdt matrix — the fused layout crosses component
boundaries and cannot shard (EXPERIMENTS.md §Perf HC2).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.lora import LoRAConfig
from repro.models.configs import ModelConfig
from repro.models.schema import build_schema, _is_p
from repro.launch.mesh import batch_axes, axis_size


def _spec_for(cfg: ModelConfig, mesh, strategy: str, shape, logical) -> P:
    msize = mesh.shape["model"]
    dsize = mesh.shape["data"]
    parts = []
    used = set()
    for dim, name in zip(shape, logical):
        ax = None
        if name == "embed":
            if strategy == "fsdp_tp" and dim % dsize == 0:
                ax = "data"
        elif name == "vocab":
            if dim % msize == 0:
                ax = "model"
        elif name == "heads":
            if cfg.n_heads % msize == 0 and dim % msize == 0:
                ax = "model"
        elif name == "kv_heads":
            if cfg.n_kv_heads % msize == 0 and dim % msize == 0:
                ax = "model"
        elif name == "heads_sep":
            if dim % msize == 0:
                ax = "model"
        elif name in ("ffn", "experts"):
            if dim % msize == 0:
                ax = "model"
        elif name in ("ssm", "ssm_heads"):
            # Mamba head-parallel TP: shard d_inner / head dims when the
            # SSM head count divides the model axis (EXPERIMENTS.md §Perf)
            if cfg.ssm is not None and cfg.n_ssm_heads % msize == 0 \
                    and dim % msize == 0:
                ax = "model"
        # periods / enc_layers / None -> replicate
        if ax in used:       # one mesh axis per spec (experts wins over ffn)
            ax = None
        if ax is not None:
            used.add(ax)
        parts.append(ax)
    return P(*parts)


def param_shardings(cfg: ModelConfig, mesh, strategy: str = "fsdp_tp"):
    """Pytree of NamedSharding matching ``schema.init_params`` structure."""
    schema = build_schema(cfg)
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, _spec_for(cfg, mesh, strategy,
                                                p.shape, p.logical)),
        schema, is_leaf=_is_p)


def replicated(mesh):
    return NamedSharding(mesh, P())


def tree_replicated(tree, mesh):
    rep = replicated(mesh)
    return jax.tree_util.tree_map(lambda _: rep, tree)


def _dim0_batch_spec(mesh, b: int, extra_dims: int) -> P:
    bx = batch_axes(mesh)
    if b % axis_size(mesh, bx) == 0:
        return P(bx, *([None] * extra_dims))
    return P(*([None] * (extra_dims + 1)))


def batch_shardings(batch, mesh):
    """Shard every bucket tensor's leading (row) dim over the batch axes."""
    def spec(x):
        if x is None:
            return None
        return NamedSharding(mesh, _dim0_batch_spec(mesh, x.shape[0],
                                                    x.ndim - 1))
    return jax.tree_util.tree_map(spec, batch)


def cache_shardings(cfg: ModelConfig, cache_abs, mesh,
                    strategy: str = "auto"):
    """Cache leaves are [Pn, B, ...]: B over batch axes when divisible;
    the widest remaining axis over "model" per the rules in the module doc.

    strategy="seq" shards the KV SEQUENCE axis over "model" instead of the
    kv-head/head_dim axes (flash-decoding layout): each model shard holds a
    contiguous slice of every row's history and computes local softmax
    partials; GSPMD then reduces tiny (m, l, acc) statistics instead of
    full attention scores — the §Perf hillclimb for GQA decode where
    n_kv_heads < model axis."""
    msize = mesh.shape["model"]
    bx = batch_axes(mesh)
    bsz = axis_size(mesh, bx)

    def leaf_spec(path, x):
        key = None
        for p in path:
            if hasattr(p, "key"):
                key = str(p.key)
        dims = [None] * x.ndim
        if x.shape[1] % bsz == 0 and x.shape[1] > 1:
            dims[1] = bx
        if key in ("k", "v", "xk", "xv"):
            # [Pn, B, S, kv, hd]
            if strategy == "seq" and x.shape[2] % msize == 0:
                dims[2] = "model"
            elif cfg.n_kv_heads % msize == 0:
                dims[3] = "model"
            elif x.shape[4] % msize == 0:
                dims[4] = "model"
            elif x.shape[2] % msize == 0:
                dims[2] = "model"
        elif key in ("ckv", "kpe"):
            # [Pn, B, S, c] — shard the sequence axis (latent stays whole)
            if x.shape[2] % msize == 0:
                dims[2] = "model"
        elif key == "h":
            # [Pn, B, nh, hd, ds]
            if x.shape[2] % msize == 0:
                dims[2] = "model"
        elif key == "conv_x":
            if x.shape[3] % msize == 0:
                dims[3] = "model"
        # conv_bc stays replicated (small, group-shared)
        return NamedSharding(mesh, P(*dims))

    return jax.tree_util.tree_map_with_path(leaf_spec, cache_abs)


def lora_shardings(bank_abs, mesh):
    """Shard each adapter's wide dim over "model": ``a``'s d_in (contraction
    — GSPMD inserts a small [T,n,r] partial-sum) and ``b``'s d_out (matches
    the base linear's output sharding).  At 100B scale the bank + its f32
    Adam moments are NOT negligible (~GBs replicated)."""
    msize = mesh.shape["model"]

    def leaf(path, x):
        key = None
        for p in path:
            if hasattr(p, "key"):
                key = str(p.key)
        dims = [None] * x.ndim
        if key == "a" and x.ndim >= 2 and x.shape[-2] % msize == 0:
            dims[-2] = "model"
        elif key == "b" and x.ndim >= 1 and x.shape[-1] % msize == 0:
            dims[-1] = "model"
        return NamedSharding(mesh, P(*dims))

    return jax.tree_util.tree_map_with_path(leaf, bank_abs)


def opt_shardings(opt_abs, mesh):
    """AdamW moments follow the bank sharding; counters replicate."""
    bank_like_m = lora_shardings(opt_abs.m, mesh)
    bank_like_v = lora_shardings(opt_abs.v, mesh)
    return type(opt_abs)(m=bank_like_m, v=bank_like_v,
                         t=replicated(mesh))


def act_constraint_fn(mesh):
    """Sequence-parallel activation constraint: shard the flattened token
    axis of the scan carry over (batch axes + model) so per-period saved
    activations fit HBM on long-sequence training."""
    bx = batch_axes(mesh)
    spec = P((*bx, "model"), None)

    def constrain(x):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return constrain


def mesh_context(mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def ambient_mesh():
    """The mesh currently in scope (empty when none is)."""
    return jax.sharding.get_abstract_mesh()


def maybe_constrain(x, *spec):
    """with_sharding_constraint that no-ops when no mesh (or no "model"
    axis) is in scope — lets model code carry expert-parallel layout hints
    without breaking single-device tests."""
    am = ambient_mesh()
    if am is None or am.empty or "model" not in am.axis_names:
        return x
    ok = all(s is None or (isinstance(s, str) and s in am.axis_names)
             or (isinstance(s, tuple) and all(a in am.axis_names for a in s))
             for s in spec)
    if not ok:
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))
