"""Explicit expert-parallel MoE (shard_map + all-to-all).

GSPMD cannot partition the sort-based dispatch scatter — it replicates the
full [G, Sk, d] update tensor across the mesh (observed: a 64 GiB all-gather
per MoE layer on jamba's train shape).  So the distributed path is explicit
SPMD: every device routes and packs ITS OWN tokens locally (scatter over a
[E, cap_local, d] buffer is device-local), one all-to-all over the "model"
axis re-shards expert buffers from token-major to expert-major, the expert
FFN runs as a dense local einsum against the device's expert slice, and a
reverse all-to-all brings results home.  Collective volume per device is
2 x k x cap_factor x T_local x d bytes — the textbook expert-parallel
schedule the paper's MoE-serving setting implies.

Falls back to ``moe.moe_apply`` (grouped vmap) when no mesh is in scope
(single-device tests / the CPU engine).
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.configs import MoEConfig
from repro.models.moe import load_balance_loss, moe_apply


def _dist_axes():
    from repro.distributed.sharding import ambient_mesh
    am = ambient_mesh()
    if am is None or am.empty or "model" not in am.axis_names:
        return None
    bx = tuple(a for a in ("pod", "data") if a in am.axis_names)
    return am, bx


def moe_apply_auto(x: jax.Array, params: dict, mcfg: MoEConfig,
                   fsdp: bool = True) -> Tuple[jax.Array, jax.Array]:
    """Dispatch: shard_map expert parallelism under a mesh, vmap fallback
    otherwise."""
    ctx = _dist_axes()
    if ctx is None:
        return moe_apply(x, params, mcfg)
    am, bx = ctx
    msize = am.shape["model"]
    E = mcfg.num_experts
    T = x.shape[0]
    chips = msize
    for a in bx:
        chips *= am.shape[a]
    if E % msize != 0 or T % chips != 0:
        return moe_apply(x, params, mcfg)

    # aux loss on the full (GSPMD-sharded) token stream — tiny einsum
    logits = x @ params["router"].astype(x.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    _, idx_full = jax.lax.top_k(probs, mcfg.top_k)
    aux = load_balance_loss(probs, idx_full, E) * mcfg.router_aux_weight

    tok_spec = P((*bx, "model"), None)
    dsize = am.shape["data"] if "data" in am.axis_names else 1
    d = x.shape[1]
    w_embed_spec = "data" if (fsdp and d % dsize == 0) else None
    w_in_spec = P("model", w_embed_spec, None)     # [E, d, f]
    w_out_spec = P("model", None, w_embed_spec)    # [E, f, d]
    r_spec = P(w_embed_spec, "model" if E % msize == 0 else None)

    cap = max(int(math.ceil((T // chips) * mcfg.top_k / E
                            * mcfg.capacity_factor)), 1)

    @functools.partial(
        jax.shard_map, mesh=am,
        in_specs=(tok_spec, r_spec, w_in_spec, w_in_spec, w_out_spec),
        out_specs=tok_spec, check_vma=False)
    def inner(xb, rb, wgb, wub, wdb):
        # un-FSDP the weight blocks (the manual analogue of GSPMD's
        # per-layer FSDP all-gather)
        if w_embed_spec is not None:
            wgb = jax.lax.all_gather(wgb, "data", axis=1, tiled=True)
            wub = jax.lax.all_gather(wub, "data", axis=1, tiled=True)
            wdb = jax.lax.all_gather(wdb, "data", axis=2, tiled=True)
            rb = jax.lax.all_gather(rb, "data", axis=0, tiled=True)
        if E % msize == 0 and rb.shape[1] != E:
            rb = jax.lax.all_gather(rb, "model", axis=1, tiled=True)
        Tl, dd = xb.shape
        k = mcfg.top_k
        lg = xb @ rb.astype(xb.dtype)
        pr = jax.nn.softmax(lg.astype(jnp.float32), axis=-1)
        gates, idx = jax.lax.top_k(pr, k)
        gates = (gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
                 ).astype(xb.dtype)
        flat_e = idx.reshape(Tl * k)
        tok_of = jnp.arange(Tl * k, dtype=jnp.int32) // k
        order = jnp.argsort(flat_e)
        sorted_e = flat_e[order]
        seg = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=sorted_e.dtype))
        pos_sorted = jnp.arange(Tl * k, dtype=jnp.int32) - seg[sorted_e]
        pos = jnp.zeros((Tl * k,), jnp.int32).at[order].set(pos_sorted)
        buf = jnp.zeros((E, cap, dd), xb.dtype)
        buf = buf.at[flat_e, pos].add(xb[tok_of], mode="drop")
        # token-major -> expert-major: [E, cap, d] -> [E/m, m*cap, d]
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                 tiled=True)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf,
                                   wgb.astype(xb.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", buf, wub.astype(xb.dtype))
        out = jnp.einsum("ecf,efd->ecd", h, wdb.astype(xb.dtype))
        out = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0,
                                 tiled=True)
        kept = (pos < cap)
        y_tok = out[flat_e, jnp.minimum(pos, cap - 1)]
        y_tok = jnp.where(kept[:, None], y_tok, 0.0)
        return jnp.einsum("tkd,tk->td", y_tok.reshape(Tl, k, dd), gates)

    y = inner(x, params["router"], params["w_gate"], params["w_up"],
              params["w_down"])
    return y, aux.astype(jnp.float32)
