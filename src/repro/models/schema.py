"""Parameter schema: one declarative walker produces (a) initialised params,
(b) abstract ShapeDtypeStructs for the dry-run, (c) PartitionSpecs from
logical-axis rules, and (d) the LoRA-target table — so all four can never
drift apart.

Layers are stacked over a leading ``periods`` axis (scan axis).  Logical axis
names used here are mapped to mesh axes by ``repro.distributed.sharding``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.configs import ModelConfig


@dataclasses.dataclass(frozen=True)
class P:
    shape: Tuple[int, ...]
    logical: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones | a_log | dt_bias
    scale: float = 0.02
    lora: bool = False            # eligible LoRA target (last 2 dims = in/out)

    def __post_init__(self):
        assert len(self.shape) == len(self.logical), (self.shape, self.logical)


def _attn_block(cfg: ModelConfig, pos: int, stack: int, axis0: str) -> Dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    blk: Dict[str, Any] = {"ln1": P((stack, d), (axis0, None), "ones")}
    if cfg.mla is not None:
        m = cfg.mla
        qd = m.qk_nope_dim + m.qk_rope_dim
        blk["wq"] = P((stack, d, h * qd), (axis0, "embed", "heads"), lora=True)
        blk["wdkv"] = P((stack, d, m.kv_lora_rank + m.qk_rope_dim),
                        (axis0, "embed", None), lora=True)
        blk["wuk"] = P((stack, m.kv_lora_rank, h, m.qk_nope_dim),
                       (axis0, None, "heads_sep", None))
        blk["wuv"] = P((stack, m.kv_lora_rank, h, m.v_head_dim),
                       (axis0, None, "heads_sep", None))
        blk["wo"] = P((stack, h * m.v_head_dim, d), (axis0, "heads", "embed"),
                      lora=True)
    else:
        blk["wq"] = P((stack, d, h * hd), (axis0, "embed", "heads"), lora=True)
        blk["wk"] = P((stack, d, kv * hd), (axis0, "embed", "kv_heads"), lora=True)
        blk["wv"] = P((stack, d, kv * hd), (axis0, "embed", "kv_heads"), lora=True)
        blk["wo"] = P((stack, h * hd, d), (axis0, "heads", "embed"), lora=True)
        if cfg.qkv_bias:
            blk["bq"] = P((stack, h * hd), (axis0, "heads"), "zeros")
            blk["bk"] = P((stack, kv * hd), (axis0, "kv_heads"), "zeros")
            blk["bv"] = P((stack, kv * hd), (axis0, "kv_heads"), "zeros")
    if cfg.is_cross_layer(pos):
        blk["xln"] = P((stack, d), (axis0, None), "ones")
        blk["xwq"] = P((stack, d, h * hd), (axis0, "embed", "heads"), lora=True)
        blk["xwk"] = P((stack, d, kv * hd), (axis0, "embed", "kv_heads"))
        blk["xwv"] = P((stack, d, kv * hd), (axis0, "embed", "kv_heads"))
        blk["xwo"] = P((stack, h * hd, d), (axis0, "heads", "embed"), lora=True)
        blk["xgate"] = P((stack,), (axis0,), "zeros")
    return blk


def _mamba_block(cfg: ModelConfig, stack: int, axis0: str) -> Dict:
    """Head-ALIGNED component projections (z, x, BC, dt are separate weights,
    NOT one fused zxBCdt matrix): the d_inner/head dims then shard cleanly
    over the mesh "model" axis (Mamba tensor parallelism) — a fused
    projection's output crosses component boundaries and would force
    per-layer resharding."""
    d, s = cfg.d_model, cfg.ssm
    di, nh = cfg.d_inner, cfg.n_ssm_heads
    gds = s.n_groups * s.d_state
    return {
        "ln1": P((stack, d), (axis0, None), "ones"),
        "in_z": P((stack, d, di), (axis0, "embed", "ssm"), lora=True),
        "in_x": P((stack, d, di), (axis0, "embed", "ssm"), lora=True),
        "in_bc": P((stack, d, 2 * gds), (axis0, "embed", None)),
        "in_dt": P((stack, d, nh), (axis0, "embed", "ssm_heads")),
        "conv_x": P((stack, s.conv_width, di), (axis0, None, "ssm")),
        "conv_bx": P((stack, di), (axis0, "ssm"), "zeros"),
        "conv_bc": P((stack, s.conv_width, 2 * gds), (axis0, None, None)),
        "conv_bbc": P((stack, 2 * gds), (axis0, None), "zeros"),
        "a_log": P((stack, nh), (axis0, "ssm_heads"), "a_log"),
        "d_skip": P((stack, nh), (axis0, "ssm_heads"), "ones"),
        "dt_bias": P((stack, nh), (axis0, "ssm_heads"), "dt_bias"),
        "mnorm": P((stack, di), (axis0, "ssm"), "ones"),
        "out_proj": P((stack, di, d), (axis0, "ssm", "embed"), lora=True),
    }


def _ffn_block(cfg: ModelConfig, pos: int, stack: int, axis0: str) -> Dict:
    d = cfg.d_model
    out: Dict[str, Any] = {"ln2": P((stack, d), (axis0, None), "ones")}
    if cfg.is_moe_layer(pos):
        e = cfg.moe
        out["router"] = P((stack, d, e.num_experts), (axis0, "embed", "experts"),
                          scale=0.006)
        out["w_gate"] = P((stack, e.num_experts, d, e.d_ff_expert),
                          (axis0, "experts", "embed", "ffn"))
        out["w_up"] = P((stack, e.num_experts, d, e.d_ff_expert),
                        (axis0, "experts", "embed", "ffn"))
        out["w_down"] = P((stack, e.num_experts, e.d_ff_expert, d),
                          (axis0, "experts", "ffn", "embed"))
        if e.num_shared:
            fs = e.num_shared * e.d_ff_expert
            out["shared"] = {
                "wg": P((stack, d, fs), (axis0, "embed", "ffn"), lora=True),
                "wu": P((stack, d, fs), (axis0, "embed", "ffn"), lora=True),
                "wd": P((stack, fs, d), (axis0, "ffn", "embed"), lora=True),
            }
    elif cfg.d_ff > 0:
        out["wg"] = P((stack, d, cfg.d_ff), (axis0, "embed", "ffn"), lora=True)
        out["wu"] = P((stack, d, cfg.d_ff), (axis0, "embed", "ffn"), lora=True)
        out["wd"] = P((stack, cfg.d_ff, d), (axis0, "ffn", "embed"), lora=True)
    else:
        return {}
    return out


def build_schema(cfg: ModelConfig) -> Dict:
    d, V = cfg.d_model, cfg.vocab
    Pn = cfg.n_periods
    blocks = []
    for pos, kind in enumerate(cfg.pattern):
        if kind == "attn":
            blk = _attn_block(cfg, pos, Pn, "periods")
        elif kind == "mamba":
            blk = _mamba_block(cfg, Pn, "periods")
        else:
            raise ValueError(kind)
        blk.update(_ffn_block(cfg, pos, Pn, "periods"))
        blocks.append(blk)
    schema: Dict[str, Any] = {
        "embed": P((V, d), ("vocab", "embed"), scale=0.02),
        "blocks": tuple(blocks),
        "final_norm": P((d,), (None,), "ones"),
    }
    if not cfg.tie_embeddings:
        schema["lm_head"] = P((d, V), ("embed", "vocab"))
    if cfg.encoder is not None:
        ne = cfg.encoder.n_layers
        h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
        eblk = {
            "ln1": P((ne, d), ("enc_layers", None), "ones"),
            "wq": P((ne, d, h * hd), ("enc_layers", "embed", "heads")),
            "wk": P((ne, d, kv * hd), ("enc_layers", "embed", "kv_heads")),
            "wv": P((ne, d, kv * hd), ("enc_layers", "embed", "kv_heads")),
            "wo": P((ne, h * hd, d), ("enc_layers", "heads", "embed")),
            "ln2": P((ne, d), ("enc_layers", None), "ones"),
            "wg": P((ne, d, cfg.d_ff), ("enc_layers", "embed", "ffn")),
            "wu": P((ne, d, cfg.d_ff), ("enc_layers", "embed", "ffn")),
            "wd": P((ne, cfg.d_ff, d), ("enc_layers", "ffn", "embed")),
        }
        schema["encoder"] = {"blocks": eblk,
                             "final_norm": P((d,), (None,), "ones")}
    return schema


def _is_p(x) -> bool:
    return isinstance(x, P)


def _init_leaf(key: jax.Array, p: P, dtype) -> jax.Array:
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    if p.init == "a_log":
        nh = p.shape[-1]
        base = jnp.log(jnp.linspace(1.0, 16.0, nh))
        return jnp.broadcast_to(base, p.shape).astype(dtype)
    if p.init == "dt_bias":
        # inverse-softplus of dt in [1e-3, 0.1]
        nh = p.shape[-1]
        dt = jnp.exp(jnp.linspace(np.log(1e-3), np.log(0.1), nh))
        base = jnp.log(jnp.expm1(dt))
        return jnp.broadcast_to(base, p.shape).astype(dtype)
    fan_in = p.shape[-2] if len(p.shape) >= 2 else p.shape[-1]
    std = min(p.scale, fan_in ** -0.5) if p.init == "normal" else p.scale
    # drawn in the target dtype: an fp32 draw of a stacked bf16 leaf is a
    # transient twice the leaf's size (3.8 GB for Llama-3-8B's [16, 4096,
    # 14336] MLP stack), which a 16 GB chip may not have beside the weights
    return (jax.random.normal(key, p.shape, dtype) * std).astype(dtype)


def init_params(cfg: ModelConfig, key: jax.Array,
                dtype=None) -> Dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    schema = build_schema(cfg)
    leaves, treedef = jax.tree_util.tree_flatten(schema, is_leaf=_is_p)
    keys = jax.random.split(key, len(leaves))
    vals = [_init_leaf(k, p, dtype) for k, p in zip(keys, leaves)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def abstract_params(cfg: ModelConfig, dtype=None) -> Dict:
    dtype = dtype or jnp.dtype(cfg.dtype)
    schema = build_schema(cfg)
    return jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, dtype), schema, is_leaf=_is_p)


@dataclasses.dataclass(frozen=True)
class LoraTarget:
    stack: Tuple[int, ...]
    d_in: int
    d_out: int


def lora_targets(cfg: ModelConfig, target_names: Tuple[str, ...]) -> Dict:
    """Pytree of LoraTarget for every eligible LoRA leaf whose key name is in
    ``target_names`` (the LoRA bank mirrors this structure)."""
    schema = build_schema(cfg)

    def walk(node, name):
        if _is_p(node):
            if node.lora and name in target_names and len(node.shape) >= 3:
                return LoraTarget(node.shape[:-2], node.shape[-2], node.shape[-1])
            return None
        if isinstance(node, dict):
            out = {k: walk(v, k) for k, v in node.items()}
            return {k: v for k, v in out.items() if v is not None}
        if isinstance(node, tuple):
            return tuple(walk(v, name) or {} for v in node)
        return None

    return {"blocks": walk(schema["blocks"], "blocks")}


def param_logical_axes(cfg: ModelConfig) -> Dict:
    """Same-structure pytree of logical-axis tuples (for sharding rules)."""
    schema = build_schema(cfg)
    return jax.tree_util.tree_map(lambda p: p.logical, schema, is_leaf=_is_p)
