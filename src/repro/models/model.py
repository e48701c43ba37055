"""Generic scanned decoder covering every assigned architecture family, with
the paper's unified computation flow built in.

``unified_forward`` implements Algorithms 1–2 of the paper: one joint
projection per linear for ALL request buckets (fine-tune/eval, prefill,
decode) via ``core.lora.dense`` (base matmul + SMLM multi-LoRA), per-bucket
attention/SSM paths, joint output projection, and per-row losses for
fine-tune/eval rows.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.lora import dense
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import quant
from repro.models.configs import ModelConfig
from repro.models.stream import DECBatch, FTBatch, ModelOut, PFBatch, UnifiedBatch


# ---------------------------------------------------------------------------
# stream plan: bucket sizes, per-token adapter ids, split/merge
# ---------------------------------------------------------------------------

class _Plan:
    def __init__(self, cfg: ModelConfig, batch: UnifiedBatch,
                 lora_scale: Optional[jax.Array]):
        ft, pf, dec = batch.ft, batch.pf, batch.dec
        self.ft, self.pf, self.dec = ft, pf, dec
        self.Bf, self.Sf = (ft.tokens.shape if ft is not None else (0, 0))
        self.Bp, self.Sp = (pf.tokens.shape if pf is not None else (0, 0))
        # decode bucket: [Bd] plain decode or [Bd, Sd] verify chunks
        if dec is not None:
            self.Bd = dec.tokens.shape[0]
            self.Sd = dec.tokens.shape[1] if dec.tokens.ndim == 2 else 1
        else:
            self.Bd, self.Sd = 0, 1
        self.Bc = self.Bd + self.Bp          # cache rows: dec first, then pf
        sizes = [self.Bf * self.Sf, self.Bp * self.Sp, self.Bd * self.Sd]
        self.sizes = sizes
        self.T = sum(sizes)
        ids = []
        if ft is not None:
            ids.append(jnp.repeat(ft.adapter, self.Sf))
        if pf is not None:
            ids.append(jnp.repeat(pf.adapter, self.Sp))
        if dec is not None:
            ids.append(jnp.repeat(dec.adapter, self.Sd))
        self.ids = jnp.concatenate(ids) if ids else None
        if lora_scale is not None and self.ids is not None:
            n = lora_scale.shape[0]
            safe = jnp.clip(self.ids, 0, n - 1)
            self.scale_t = lora_scale[safe]
        else:
            self.scale_t = None
        # positions / validity per bucket
        if ft is not None:
            self.ft_pos = jnp.broadcast_to(jnp.arange(self.Sf, dtype=jnp.int32),
                                           (self.Bf, self.Sf))
            self.ft_valid = ft.mask
        if pf is not None:
            ar = jnp.arange(self.Sp, dtype=jnp.int32)
            # suffix-only prefill: row positions start at the cached span
            self.pf_cached = pf.cached_len
            if pf.cached_len is not None:
                self.pf_pos = pf.cached_len[:, None] + ar[None, :]
            else:
                self.pf_pos = jnp.broadcast_to(ar, (self.Bp, self.Sp))
            self.pf_valid = ar[None, :] < pf.length[:, None]
        else:
            self.pf_cached = None
        if dec is not None:
            self.dec_pos = dec.pos
            # per-query positions of the (1 + k)-token chunk, and per-row
            # valid chunk lengths (trailing draft slots may be padding)
            ard = jnp.arange(self.Sd, dtype=jnp.int32)
            self.dec_qpos = dec.pos[:, None] + ard[None, :]
            self.dec_len = (dec.length if dec.length is not None
                            else jnp.full((self.Bd,), self.Sd, jnp.int32))
        # paged-layout block tables (None -> dense row layout per bucket)
        self.pf_tables = pf.block_tables if pf is not None else None
        self.dec_tables = dec.block_tables if dec is not None else None

    def split(self, x: jax.Array):
        """[T, ...] -> (xf [Bf,Sf,...], xp [Bp,Sp,...], xd [Bd,Sd,...])"""
        t0, t1, _ = self.sizes
        rest = x.shape[1:]
        xf = x[:t0].reshape(self.Bf, self.Sf, *rest) if t0 else None
        xp = x[t0:t0 + t1].reshape(self.Bp, self.Sp, *rest) if t1 else None
        xd = x[t0 + t1:].reshape(self.Bd, self.Sd, *rest) if self.Bd else None
        return xf, xp, xd

def _merge_flat(plan: _Plan, xf, xp, xd) -> jax.Array:
    parts = []
    if xf is not None:
        parts.append(xf.reshape(plan.sizes[0], -1))
    if xp is not None:
        parts.append(xp.reshape(plan.sizes[1], -1))
    if xd is not None:
        parts.append(xd.reshape(plan.sizes[2], -1))
    return jnp.concatenate(parts, axis=0)


# ---------------------------------------------------------------------------
# KV / state cache
#
# Two layouts share the same pytree structure ({"layers": tuple of dicts}):
#
# * dense  (init_cache):       every leaf is [n_periods, n_rows, ...] — one
#   row per resident request, s_max key/value slots each.  Prefill writes at
#   rows [Bd, Bd+Bp); decode updates rows [0, Bd) in place.
# * paged  (init_paged_cache): attention K/V (or MLA latents) become a flat
#   block pool [n_periods, n_blocks, block_size, ...] addressed through
#   per-request block tables carried in the batch (PFBatch/DECBatch
#   .block_tables); only per-request state that does not grow with the
#   sequence (Mamba SSM/conv state, cross-attention K/V) keeps dense rows.
#   Paged mode is selected per bucket by the presence of block tables.
# ---------------------------------------------------------------------------

# cache leaves that stay per-request rows even in the paged layout
STATE_KEYS = frozenset({"h", "conv_x", "conv_bc", "xk", "xv"})


def cache_seq_len(cfg: ModelConfig, s_max: int) -> int:
    w = cfg.sliding_window
    return min(s_max, w) if w > 0 else s_max


def init_cache(cfg: ModelConfig, n_rows: int, s_max: int,
               dtype=None) -> Dict:
    """Allocate the cache pytree: a tuple over pattern positions, each leaf
    stacked [n_periods, n_rows, ...]."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    Pn, kv, hd = cfg.n_periods, cfg.n_kv_heads, cfg.hd
    sc = cache_seq_len(cfg, s_max)
    per_pos = []
    for pos, kind in enumerate(cfg.pattern):
        c: Dict[str, jax.Array] = {}
        if kind == "attn":
            if cfg.mla is not None:
                m = cfg.mla
                c["ckv"] = jnp.zeros((Pn, n_rows, sc, m.kv_lora_rank), dtype)
                c["kpe"] = jnp.zeros((Pn, n_rows, sc, m.qk_rope_dim), dtype)
            else:
                c["k"] = jnp.zeros((Pn, n_rows, sc, kv, hd), dtype)
                c["v"] = jnp.zeros((Pn, n_rows, sc, kv, hd), dtype)
            if cfg.is_cross_layer(pos):
                f = cfg.encoder.n_frames if cfg.encoder else cfg.n_img_tokens
                c["xk"] = jnp.zeros((Pn, n_rows, f, kv, hd), dtype)
                c["xv"] = jnp.zeros((Pn, n_rows, f, kv, hd), dtype)
        elif kind == "mamba":
            s = cfg.ssm
            nh, hdm = cfg.n_ssm_heads, s.head_dim
            gds = s.n_groups * s.d_state
            c["h"] = jnp.zeros((Pn, n_rows, nh, hdm, s.d_state), dtype)
            c["conv_x"] = jnp.zeros((Pn, n_rows, s.conv_width - 1,
                                     cfg.d_inner), dtype)
            c["conv_bc"] = jnp.zeros((Pn, n_rows, s.conv_width - 1,
                                      2 * gds), dtype)
        per_pos.append(c)
    return {"layers": tuple(per_pos)}


def init_paged_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                     n_rows: int, dtype=None) -> Dict:
    """Allocate the paged cache pytree: attention K/V as a flat block pool
    ``[n_periods, n_blocks, block_size, ...]`` shared by all requests via
    block tables; sequence-length-independent state keeps ``n_rows`` dense
    rows.  Rolling (sliding-window) buffers and paging don't compose."""
    if cfg.sliding_window > 0:
        raise ValueError("paged cache does not support sliding windows")
    dtype = dtype or jnp.dtype(cfg.dtype)
    Pn, kv, hd = cfg.n_periods, cfg.n_kv_heads, cfg.hd
    per_pos = []
    for pos, kind in enumerate(cfg.pattern):
        c: Dict[str, jax.Array] = {}
        if kind == "attn":
            if cfg.mla is not None:
                m = cfg.mla
                c["ckv"] = jnp.zeros((Pn, n_blocks, block_size,
                                      m.kv_lora_rank), dtype)
                c["kpe"] = jnp.zeros((Pn, n_blocks, block_size,
                                      m.qk_rope_dim), dtype)
            else:
                c["k"] = jnp.zeros((Pn, n_blocks, block_size, kv, hd), dtype)
                c["v"] = jnp.zeros((Pn, n_blocks, block_size, kv, hd), dtype)
            if cfg.is_cross_layer(pos):
                f = cfg.encoder.n_frames if cfg.encoder else cfg.n_img_tokens
                c["xk"] = jnp.zeros((Pn, n_rows, f, kv, hd), dtype)
                c["xv"] = jnp.zeros((Pn, n_rows, f, kv, hd), dtype)
        elif kind == "mamba":
            s = cfg.ssm
            nh, hdm = cfg.n_ssm_heads, s.head_dim
            gds = s.n_groups * s.d_state
            c["h"] = jnp.zeros((Pn, n_rows, nh, hdm, s.d_state), dtype)
            c["conv_x"] = jnp.zeros((Pn, n_rows, s.conv_width - 1,
                                     cfg.d_inner), dtype)
            c["conv_bc"] = jnp.zeros((Pn, n_rows, s.conv_width - 1,
                                      2 * gds), dtype)
        per_pos.append(c)
    return {"layers": tuple(per_pos)}


def abstract_cache(cfg: ModelConfig, n_rows: int, s_max: int, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    tree = jax.eval_shape(lambda: init_cache(cfg, n_rows, s_max, dtype))
    return tree


# -- paged pool access.  Every cache leaf is stacked over layers; a pool leaf
#    is [L, nb, bs, ...].  The helpers take the stacked leaf and the layer
#    index ``idx`` and address that layer's blocks in place, through the flat
#    ``[L*nb, bs, ...]`` view (a bitcast on TPU) with the block tables offset
#    by ``idx * nb``: the layer is never sliced out and written back, which
#    would move its whole pool twice per layer.  Tables are null-padded with
#    0, so padding entries land on block ``idx * nb``, the layer's reserved
#    garbage block; invalid positions are masked by k_valid. -------------

def _cache_value(x: jax.Array, dtype) -> jax.Array:
    """What a cache write stores.  No derivative flows through the cache,
    which spares the grad step the scatters' derivative (a select over the
    whole pool per write).  The fine-tune loss reads fine-tune rows, which
    attend to their own K/V and never to the cache, so its gradient is
    unchanged; a router's balance loss reads every row, and loses the part
    of its gradient that would pass through the inference rows' freshly
    written K/V."""
    return jax.lax.stop_gradient(x).astype(dtype)


def _flat_pool(pool: jax.Array, tables: jax.Array,
               idx: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(``[L*nb, bs, ...]`` view of the stacked pool, tables clamped and
    offset to layer ``idx``'s blocks)."""
    return (pool.reshape(-1, *pool.shape[2:]),
            jnp.maximum(tables, 0) + idx * pool.shape[1])


def _paged_write_prompt(pool: jax.Array, xh: jax.Array, tables: jax.Array,
                        idx: jax.Array) -> jax.Array:
    """Scatter prefill writes ``[Bp, Sp, ...]`` into layer ``idx``'s blocks
    of the stacked pool via tables ``[Bp, nbt]``; positions beyond
    ``nbt * block_size`` are dropped (they are padding beyond the context
    limit)."""
    flat, tbl = _flat_pool(pool, tables, idx)
    bs = pool.shape[2]
    Bp, Sp = xh.shape[:2]
    nbp = min(-(-Sp // bs), tables.shape[1])
    Lp = nbp * bs
    if Sp < Lp:
        xh = jnp.pad(xh, ((0, 0), (0, Lp - Sp)) + ((0, 0),) * (xh.ndim - 2))
    else:
        xh = xh[:, :Lp]
    xb = xh.reshape(Bp, nbp, bs, *xh.shape[2:])
    flat = flat.at[tbl[:, :nbp]].set(_cache_value(xb, pool.dtype))
    return flat.reshape(pool.shape)


def _paged_write_chunk(pool: jax.Array, xh: jax.Array, tables: jax.Array,
                       idx: jax.Array, pos: jax.Array,
                       length: jax.Array) -> jax.Array:
    """Scatter a chunk ``[Bd, Sd, ...]`` (a decode token, a verify chunk or
    a suffix prefill) into layer ``idx``'s blocks of the stacked pool: row
    ``b``'s token ``j`` lands at position ``pos[b] + j``; positions at or
    beyond ``length[b]`` (padding / unfilled draft slots) are redirected to
    the layer's null block so they cannot corrupt live cache state."""
    flat, tbl = _flat_pool(pool, tables, idx)
    bs = pool.shape[2]
    Bd, Sd = xh.shape[:2]
    j = jnp.arange(Sd, dtype=jnp.int32)[None, :]
    p = pos[:, None].astype(jnp.int32) + j                     # [Bd, Sd]
    valid = j < length[:, None]
    bi = jnp.clip(p // bs, 0, tbl.shape[1] - 1)
    bid = jnp.where(valid, jnp.take_along_axis(tbl, bi, axis=1),
                    idx * pool.shape[1])
    xf = xh.reshape(Bd * Sd, *xh.shape[2:])
    flat = flat.at[bid.reshape(-1), (p % bs).reshape(-1)].set(
        _cache_value(xf, pool.dtype))
    return flat.reshape(pool.shape)


def _paged_view(pool: jax.Array, tables: jax.Array,
                idx: jax.Array) -> jax.Array:
    """Gather per-request contiguous views ``[Bd, nbt*bs, ...]`` of layer
    ``idx``'s blocks, read from the stacked pool in place — the jnp
    reference of what kernels.decode_attn.paged_attention streams
    block-by-block without materializing."""
    flat, tbl = _flat_pool(pool, tables, idx)
    Bd, nbt = tbl.shape
    return flat[tbl].reshape(Bd, nbt * pool.shape[2], *pool.shape[3:])


def _row_write_prompt(c: jax.Array, xh: jax.Array, idx: jax.Array,
                      row0: int) -> jax.Array:
    """Write prefill rows ``[Bp, Sp, ...]`` at rows ``row0 ..`` of layer
    ``idx`` of a stacked dense-row cache ``[L, n_rows, sc, ...]``; a rolling
    (sliding-window) buffer keeps the last ``sc`` positions."""
    Bp, Sp = xh.shape[:2]
    sc = c.shape[2]
    if Sp <= sc:
        return c.at[idx, row0:row0 + Bp, :Sp].set(_cache_value(xh, c.dtype))
    rows = row0 + jnp.arange(Bp)[:, None]
    sl = (jnp.arange(Sp - sc, Sp) % sc)[None, :]
    return c.at[idx, rows, sl].set(_cache_value(xh[:, -sc:], c.dtype))


def _row_write_token(c: jax.Array, xh: jax.Array, idx: jax.Array,
                     dpos: jax.Array) -> jax.Array:
    """Write decode chunks ``[Bd, Sd, ...]`` of rows ``0 .. Bd`` at slots
    ``dpos % sc`` of layer ``idx`` of a stacked dense-row cache."""
    rows = jnp.arange(xh.shape[0])[:, None]
    return c.at[idx, rows, dpos % c.shape[2]].set(_cache_value(xh, c.dtype))


def _paged_chunk_mask(tables: jax.Array, block_size: int, pos: jax.Array,
                      length: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(k_pos, k_valid) for a verify chunk: after the chunk write the cache
    holds positions ``0 .. pos + length - 1``; within-chunk causality comes
    from the attention mask's q_pos/k_pos comparison."""
    Bd, nbt = tables.shape
    j = jnp.arange(nbt * block_size, dtype=jnp.int32)[None, :]
    k_pos = jnp.broadcast_to(j, (Bd, nbt * block_size))
    k_valid = j < pos[:, None] + length[:, None]
    return k_pos, k_valid


# recognized REPRO_PAGED_ATTN_KERNEL values (after lowercasing); "1" is a
# legacy alias normalized to "tpu" so step-cache keys stay canonical
_KERNEL_OFF = ("", "0", "off", "false")
_KERNEL_MODES = ("interpret", "tpu", "splitk", "splitk-interpret")


def _paged_kernel_mode() -> str:
    """Paged attention backend flag (ROADMAP item): empty = jnp gather view
    (the CPU default); ``interpret`` = sequential Pallas kernels in
    interpret mode (CI-testable); ``tpu`` (or ``1``) = compiled sequential
    kernels (real-TPU path); ``splitk`` / ``splitk-interpret`` = the
    flash-decoding split-K decode/verify kernels (``kernels.splitk``) with
    per-shape ``kernels.autotune`` tile/split selection.  Anything else is
    a loud error — a typo must not silently select the compiled-TPU path.
    Read at trace time — step builders key their compile cache on it."""
    import os
    v = os.environ.get("REPRO_PAGED_ATTN_KERNEL", "").strip().lower()
    if v in _KERNEL_OFF:
        return ""
    if v == "1":
        return "tpu"
    if v not in _KERNEL_MODES:
        raise ValueError(
            f"REPRO_PAGED_ATTN_KERNEL={v!r} is not a recognized paged "
            f"attention kernel mode; expected one of "
            f"{('off',) + _KERNEL_MODES} (or '1' as an alias for 'tpu')")
    return v


def _dec_cache_pos(pos: jax.Array, sc: int) -> Tuple[jax.Array, jax.Array]:
    """Per-row (k_pos [Bd, sc], k_valid [Bd, sc]) for a (possibly rolling)
    cache AFTER the current token at ``pos`` has been written."""
    j = jnp.arange(sc, dtype=jnp.int32)[None, :]
    p = pos[:, None]
    k_pos = j + sc * jnp.floor_divide(p - j, sc)
    k_valid = j <= p
    return k_pos, k_valid


# ---------------------------------------------------------------------------
# attention block
# ---------------------------------------------------------------------------

def _rope_heads(x: jax.Array, pos: jax.Array, n: int, theta: float) -> jax.Array:
    """reshape [B,S,n*hd] -> rope -> [B,S,n,hd]"""
    B, S = x.shape[:2]
    return L.rope(x.reshape(B, S, n, -1), pos, theta)


def _attn_apply(cfg: ModelConfig, pos_idx: int, p: Dict, lr: Dict,
                plan: _Plan, x: jax.Array, cache: Dict, idx: jax.Array,
                attn_chunk: int) -> Tuple[jax.Array, Dict]:
    """Attention sublayer of layer ``idx``; ``cache`` holds the leaves
    stacked over layers, read and written at ``idx`` in place."""
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    W = cfg.sliding_window
    xn = L.rms_norm(x, p["ln1"], cfg.rms_eps)
    dn = functools.partial(dense, ids=plan.ids, scale_t=plan.scale_t)
    new_cache = dict(cache)
    Bd = plan.Bd

    if cfg.mla is not None:
        out = _mla_apply(cfg, p, lr, plan, xn, cache, new_cache, idx,
                         attn_chunk)
    else:
        q = dn(xn, p["wq"], p.get("bq"), lr.get("wq"))
        k = dn(xn, p["wk"], p.get("bk"), lr.get("wk"))
        v = dn(xn, p["wv"], p.get("bv"), lr.get("wv"))
        qf, qp, qd = plan.split(q)
        kf, kp, kd = plan.split(k)
        vf, vp, vd = plan.split(v)
        outs = [None, None, None]
        if qf is not None:       # fine-tune / eval: differentiable causal path
            qh = _rope_heads(qf, plan.ft_pos, h, cfg.rope_theta)
            kh = _rope_heads(kf, plan.ft_pos, kv, cfg.rope_theta)
            vh = vf.reshape(plan.Bf, plan.Sf, kv, hd)
            outs[0] = L.attention(qh, kh, vh, q_pos=plan.ft_pos,
                                  k_pos=plan.ft_pos, k_valid=plan.ft_valid,
                                  causal=True, window=W, chunk=attn_chunk)
        if qp is not None:       # prefill: causal + cache write
            qh = _rope_heads(qp, plan.pf_pos, h, cfg.rope_theta)
            kh = _rope_heads(kp, plan.pf_pos, kv, cfg.rope_theta)
            vh = vp.reshape(plan.Bp, plan.Sp, kv, hd)
            if plan.pf_tables is not None and plan.pf_cached is not None:
                # suffix-only prefill: scatter the suffix K/V at its offset
                # (never touching shared prefix blocks — all writes land at
                # positions >= cached_len), then attend over the pooled view
                # so cached prefix tokens are READ instead of recomputed
                ck = _paged_write_chunk(new_cache["k"], kh, plan.pf_tables,
                                        idx, plan.pf_cached, plan.pf.length)
                cv = _paged_write_chunk(new_cache["v"], vh, plan.pf_tables,
                                        idx, plan.pf_cached, plan.pf.length)
                new_cache["k"], new_cache["v"] = ck, cv
                mode = _paged_kernel_mode()
                if mode:
                    from repro.kernels.decode_attn import paged_attention
                    kf, tbl = _flat_pool(ck, plan.pf_tables, idx)
                    vf, _ = _flat_pool(cv, plan.pf_tables, idx)
                    outs[1] = paged_attention(
                        qh, kf, vf, tbl, plan.pf_cached, plan.pf.length,
                        interpret=mode.endswith("interpret"))
                else:
                    k_pos, k_valid = _paged_chunk_mask(
                        plan.pf_tables, ck.shape[2], plan.pf_cached,
                        plan.pf.length)
                    outs[1] = L.attention(
                        qh, _paged_view(ck, plan.pf_tables, idx),
                        _paged_view(cv, plan.pf_tables, idx),
                        q_pos=plan.pf_pos, k_pos=k_pos, k_valid=k_valid,
                        causal=True, window=0, chunk=attn_chunk)
            else:
                outs[1] = L.attention(qh, kh, vh, q_pos=plan.pf_pos,
                                      k_pos=plan.pf_pos,
                                      k_valid=plan.pf_valid,
                                      causal=True, window=W,
                                      chunk=attn_chunk)
            if plan.pf_tables is not None and plan.pf_cached is None:
                # paged full-prompt prefill: straight into the blocks
                new_cache["k"] = _paged_write_prompt(new_cache["k"], kh,
                                                     plan.pf_tables, idx)
                new_cache["v"] = _paged_write_prompt(new_cache["v"], vh,
                                                     plan.pf_tables, idx)
            elif plan.pf_tables is None:
                new_cache["k"] = _row_write_prompt(new_cache["k"], kh, idx,
                                                   Bd)
                new_cache["v"] = _row_write_prompt(new_cache["v"], vh, idx,
                                                   Bd)
        if qd is not None:       # decode / verify: (1 + k)-token chunk
            Sd = plan.Sd
            dpos = plan.dec_qpos                               # [Bd, Sd]
            qh = _rope_heads(qd, dpos, h, cfg.rope_theta)      # [Bd,Sd,h,hd]
            kh = _rope_heads(kd, dpos, kv, cfg.rope_theta)
            vh = vd.reshape(plan.Bd, Sd, kv, hd)
            if plan.dec_tables is not None:  # paged: block-table gather
                ck = _paged_write_chunk(new_cache["k"], kh, plan.dec_tables,
                                        idx, plan.dec_pos, plan.dec_len)
                cv = _paged_write_chunk(new_cache["v"], vh, plan.dec_tables,
                                        idx, plan.dec_pos, plan.dec_len)
                new_cache["k"], new_cache["v"] = ck, cv
                mode = _paged_kernel_mode()
                if mode:
                    # real-TPU path: block tables walked by the DMA engine.
                    # splitk modes partition the walk across grid cells and
                    # LSE-merge the partials (flash decoding); the autotune
                    # table/heuristic picks the fan-out per shape at trace
                    # time.
                    interp = mode.endswith("interpret")
                    kf, tbl = _flat_pool(ck, plan.dec_tables, idx)
                    vf, _ = _flat_pool(cv, plan.dec_tables, idx)
                    if mode.startswith("splitk"):
                        from repro.kernels.autotune import choose
                        from repro.kernels.splitk import \
                            paged_verify_attention_splitk
                        kc = choose(hd, ck.shape[2],
                                    plan.dec_tables.shape[1], plan.Bd * kv)
                        outs[2] = paged_verify_attention_splitk(
                            qh, kf, vf, tbl, plan.dec_pos, plan.dec_len,
                            num_splits=kc.num_splits, interpret=interp)
                    else:
                        from repro.kernels.decode_attn import paged_attention
                        outs[2] = paged_attention(
                            qh, kf, vf, tbl, plan.dec_pos, plan.dec_len,
                            interpret=interp)
                else:
                    k_pos, k_valid = _paged_chunk_mask(
                        plan.dec_tables, ck.shape[2], plan.dec_pos,
                        plan.dec_len)
                    outs[2] = L.attention(
                        qh, _paged_view(ck, plan.dec_tables, idx),
                        _paged_view(cv, plan.dec_tables, idx), q_pos=dpos,
                        k_pos=k_pos, k_valid=k_valid, causal=True, window=0)
            else:
                if Sd > 1 and W > 0:
                    raise NotImplementedError(
                        "verify chunks need a non-rolling cache: rolled-back "
                        "draft positions would alias live window slots")
                sc = cache["k"].shape[2]
                ck = _row_write_token(new_cache["k"], kh, idx, dpos)
                cv = _row_write_token(new_cache["v"], vh, idx, dpos)
                new_cache["k"], new_cache["v"] = ck, cv
                k_pos, k_valid = _dec_cache_pos(
                    plan.dec_pos + plan.dec_len - 1, sc)
                outs[2] = L.attention(qh, ck[idx, :Bd], cv[idx, :Bd],
                                      q_pos=dpos, k_pos=k_pos,
                                      k_valid=k_valid, causal=True, window=0)
        out = _merge_flat(plan, *outs)
    o = dn(out, p["wo"], None, lr.get("wo"))
    x = x + o

    if cfg.is_cross_layer(pos_idx):
        x = _cross_apply(cfg, p, lr, plan, x, cache, new_cache, idx,
                         attn_chunk)
    return x, new_cache


def _mla_apply(cfg: ModelConfig, p: Dict, lr: Dict, plan: _Plan,
               xn: jax.Array, cache: Dict, new_cache: Dict, idx: jax.Array,
               attn_chunk: int = 0) -> jax.Array:
    """Absorbed-form MLA for all buckets; the cache holds the latent,
    stacked over layers and addressed at layer ``idx`` in place."""
    m, h = cfg.mla, cfg.n_heads
    dnp, dr, c_rank = m.qk_nope_dim, m.qk_rope_dim, m.kv_lora_rank
    dn = functools.partial(dense, ids=plan.ids, scale_t=plan.scale_t)
    q = dn(xn, p["wq"], None, lr.get("wq"))              # [T, h*(dn+dr)]
    ckv_full = dn(xn, p["wdkv"], None, lr.get("wdkv"))   # [T, c + dr]
    Bd = plan.Bd
    qf, qp, qd = plan.split(q)
    cf, cp, cd = plan.split(ckv_full)
    outs = [None, None, None]

    def _split_q(qb, B, S):
        qb = qb.reshape(B, S, h, dnp + dr)
        return qb[..., :dnp], qb[..., dnp:]

    def _split_c(cb):
        return cb[..., :c_rank], cb[..., c_rank:]

    if qf is not None:
        qn, qr = _split_q(qf, plan.Bf, plan.Sf)
        qr = L.rope(qr, plan.ft_pos, cfg.rope_theta)
        ckv, kpe = _split_c(cf)
        kpe = L.rope(kpe[..., None, :], plan.ft_pos, cfg.rope_theta)[..., 0, :]
        outs[0] = L.mla_attention(qn, qr, ckv, kpe, p["wuk"], p["wuv"],
                                  q_pos=plan.ft_pos, k_pos=plan.ft_pos,
                                  k_valid=plan.ft_valid, causal=True,
                                  window=cfg.sliding_window,
                                  chunk=attn_chunk)
    if qp is not None:
        qn, qr = _split_q(qp, plan.Bp, plan.Sp)
        qr = L.rope(qr, plan.pf_pos, cfg.rope_theta)
        ckv, kpe = _split_c(cp)
        kpe = L.rope(kpe[..., None, :], plan.pf_pos, cfg.rope_theta)[..., 0, :]
        if plan.pf_tables is not None and plan.pf_cached is not None:
            # suffix-only prefill: offset-scatter the latent, attend over
            # the pooled view so the cached prefix latent is read, not
            # recomputed (same contract as the standard-attention path)
            cc = _paged_write_chunk(new_cache["ckv"], ckv, plan.pf_tables,
                                    idx, plan.pf_cached, plan.pf.length)
            ce = _paged_write_chunk(new_cache["kpe"], kpe, plan.pf_tables,
                                    idx, plan.pf_cached, plan.pf.length)
            new_cache["ckv"], new_cache["kpe"] = cc, ce
            k_pos, k_valid = _paged_chunk_mask(plan.pf_tables, cc.shape[2],
                                               plan.pf_cached, plan.pf.length)
            outs[1] = L.mla_attention(qn, qr,
                                      _paged_view(cc, plan.pf_tables, idx),
                                      _paged_view(ce, plan.pf_tables, idx),
                                      p["wuk"], p["wuv"], q_pos=plan.pf_pos,
                                      k_pos=k_pos, k_valid=k_valid,
                                      causal=True, window=0,
                                      chunk=attn_chunk)
        else:
            outs[1] = L.mla_attention(qn, qr, ckv, kpe, p["wuk"], p["wuv"],
                                      q_pos=plan.pf_pos, k_pos=plan.pf_pos,
                                      k_valid=plan.pf_valid, causal=True,
                                      window=cfg.sliding_window,
                                      chunk=attn_chunk)
        if plan.pf_tables is not None and plan.pf_cached is None:
            # paged full-prompt prefill: straight into the blocks
            new_cache["ckv"] = _paged_write_prompt(new_cache["ckv"], ckv,
                                                   plan.pf_tables, idx)
            new_cache["kpe"] = _paged_write_prompt(new_cache["kpe"], kpe,
                                                   plan.pf_tables, idx)
        elif plan.pf_tables is None:
            new_cache["ckv"] = _row_write_prompt(new_cache["ckv"], ckv, idx,
                                                 Bd)
            new_cache["kpe"] = _row_write_prompt(new_cache["kpe"], kpe, idx,
                                                 Bd)
    if qd is not None:
        Sd = plan.Sd
        dpos = plan.dec_qpos                                   # [Bd, Sd]
        qn, qr = _split_q(qd, plan.Bd, Sd)
        qr = L.rope(qr, dpos, cfg.rope_theta)
        ckv, kpe = _split_c(cd)
        kpe = L.rope(kpe[..., None, :], dpos, cfg.rope_theta)[..., 0, :]
        if plan.dec_tables is not None:      # paged: block-table gather
            cc = _paged_write_chunk(new_cache["ckv"], ckv, plan.dec_tables,
                                    idx, plan.dec_pos, plan.dec_len)
            ce = _paged_write_chunk(new_cache["kpe"], kpe, plan.dec_tables,
                                    idx, plan.dec_pos, plan.dec_len)
            new_cache["ckv"], new_cache["kpe"] = cc, ce
            k_pos, k_valid = _paged_chunk_mask(plan.dec_tables, cc.shape[2],
                                               plan.dec_pos, plan.dec_len)
            outs[2] = L.mla_attention(qn, qr,
                                      _paged_view(cc, plan.dec_tables, idx),
                                      _paged_view(ce, plan.dec_tables, idx),
                                      p["wuk"], p["wuv"], q_pos=dpos,
                                      k_pos=k_pos, k_valid=k_valid,
                                      causal=True, window=0)
        else:
            if Sd > 1 and cfg.sliding_window > 0:
                raise NotImplementedError(
                    "verify chunks need a non-rolling cache: rolled-back "
                    "draft positions would alias live window slots")
            sc = cache["ckv"].shape[2]
            cc = _row_write_token(new_cache["ckv"], ckv, idx, dpos)
            ce = _row_write_token(new_cache["kpe"], kpe, idx, dpos)
            new_cache["ckv"], new_cache["kpe"] = cc, ce
            k_pos, k_valid = _dec_cache_pos(plan.dec_pos + plan.dec_len - 1,
                                            sc)
            outs[2] = L.mla_attention(qn, qr, cc[idx, :Bd], ce[idx, :Bd],
                                      p["wuk"], p["wuv"], q_pos=dpos,
                                      k_pos=k_pos, k_valid=k_valid,
                                      causal=True, window=0)
    return _merge_flat(plan, *outs)


def _cross_apply(cfg: ModelConfig, p: Dict, lr: Dict, plan: _Plan,
                 x: jax.Array, cache: Dict, new_cache: Dict, idx: jax.Array,
                 attn_chunk: int = 0) -> jax.Array:
    """Cross-attention sublayer (VLM image layers / enc-dec decoder); the
    cross K/V rows are stacked over layers and addressed at ``idx``."""
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dn = functools.partial(dense, ids=plan.ids, scale_t=plan.scale_t)
    xn = L.rms_norm(x, p["xln"], cfg.rms_eps)
    q = dn(xn, p["xwq"], None, lr.get("xwq"))
    qf, qp, qd = plan.split(q)
    Bd = plan.Bd
    outs = [None, None, None]

    def _kv_from(src):           # src: [B, F, d] cross source embeddings
        B, F = src.shape[:2]
        kx = (src.reshape(B * F, -1) @ p["xwk"].astype(src.dtype)
              ).reshape(B, F, kv, hd)
        vx = (src.reshape(B * F, -1) @ p["xwv"].astype(src.dtype)
              ).reshape(B, F, kv, hd)
        return kx, vx

    def _xattn(qb, kx, vx, qpos):
        B, S = qb.shape[:2]
        F = kx.shape[1]
        valid = jnp.ones((B, F), bool)
        kpos = jnp.zeros((B, F), jnp.int32)
        return L.attention(qb.reshape(B, S, h, hd), kx, vx,
                           q_pos=qpos, k_pos=kpos, k_valid=valid,
                           causal=False, window=0, chunk=attn_chunk)

    if qf is not None:
        src = plan.ft.aux_embed.astype(x.dtype)
        kx, vx = _kv_from(src)
        outs[0] = _xattn(qf, kx, vx, plan.ft_pos)
    if qp is not None:
        src = plan.pf.aux_embed.astype(x.dtype)
        kx, vx = _kv_from(src)
        outs[1] = _xattn(qp, kx, vx, plan.pf_pos)
        for key, val in (("xk", kx), ("xv", vx)):
            c = new_cache[key]
            new_cache[key] = c.at[idx, Bd:Bd + plan.Bp].set(
                _cache_value(val, c.dtype))
    if qd is not None:
        kx, vx = cache["xk"][idx, :Bd], cache["xv"][idx, :Bd]
        outs[2] = _xattn(qd, kx, vx, plan.dec_qpos)
    o = _merge_flat(plan, *outs)
    o = dn(o, p["xwo"], None, lr.get("xwo"))
    if "xgate" in p:
        o = jnp.tanh(p["xgate"]).astype(o.dtype) * o
    return x + o


# ---------------------------------------------------------------------------
# mamba block
# ---------------------------------------------------------------------------

def _mamba_apply(cfg: ModelConfig, p: Dict, lr: Dict, plan: _Plan,
                 x: jax.Array, cache: Dict,
                 idx: jax.Array) -> Tuple[jax.Array, Dict]:
    """Mamba2 sublayer of layer ``idx``; the state rows are stacked over
    layers and addressed at ``idx`` in place."""
    s = cfg.ssm
    di, nh, hdm = cfg.d_inner, cfg.n_ssm_heads, s.head_dim
    gds = s.n_groups * s.d_state
    xn = L.rms_norm(x, p["ln1"], cfg.rms_eps)
    dnf = functools.partial(dense, ids=plan.ids, scale_t=plan.scale_t)
    # head-aligned component projections (shardable Mamba TP; see schema)
    z_all = dnf(xn, p["in_z"], None, lr.get("in_z"))         # [T, di]
    x_all = dnf(xn, p["in_x"], None, lr.get("in_x"))         # [T, di]
    bc_all = xn @ p["in_bc"].astype(xn.dtype)                # [T, 2*gds]
    dt_all = xn @ p["in_dt"].astype(xn.dtype)                # [T, nh]
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    new_cache = dict(cache)
    zf, zp, zd = plan.split(z_all)
    xf, xp_, xd = plan.split(x_all)
    bf, bp, bd = plan.split(bc_all)
    df, dp_, dd = plan.split(dt_all)
    outs = [None, None, None]
    Bd = plan.Bd

    def _expand_bc(y_bc, lead):
        b2 = y_bc[..., :gds].reshape(*lead, s.n_groups, s.d_state)
        c2 = y_bc[..., gds:].reshape(*lead, s.n_groups, s.d_state)
        return M.expand_groups(b2, nh), M.expand_groups(c2, nh)

    def _seq(xb, bcb, dtb, valid, conv0x, conv0bc, h0):
        B, S = xb.shape[:2]
        y_x, convx_fin = M.causal_conv(xb, p["conv_x"], p["conv_bx"], conv0x)
        y_bc, convbc_fin = M.causal_conv(bcb, p["conv_bc"], p["conv_bbc"],
                                         conv0bc)
        y_x, y_bc = jax.nn.silu(y_x), jax.nn.silu(y_bc)
        xs2 = y_x.reshape(B, S, nh, hdm)
        b2, c2 = _expand_bc(y_bc, (B, S))
        dtv = jax.nn.softplus(dtb.astype(jnp.float32)
                              + p["dt_bias"].astype(jnp.float32))
        dtv = dtv * valid[..., None].astype(jnp.float32)   # pad -> no-op
        y, h_fin = M.ssd_chunked(xs2, dtv, A, b2, c2, s.chunk, h0)
        y = y + p["d_skip"].astype(y.dtype)[None, None, :, None] * xs2
        return y.reshape(B, S, di), h_fin, convx_fin, convbc_fin

    if zf is not None:
        y, _, _, _ = _seq(xf, bf, df, plan.ft_valid, None, None, None)
        outs[0] = _gated_out(y, zf, p)
    if zp is not None:
        y, h_fin, cx_fin, cbc_fin = _seq(xp_, bp, dp_, plan.pf_valid,
                                         None, None, None)
        outs[1] = _gated_out(y, zp, p)
        for key, val in (("h", h_fin), ("conv_x", cx_fin),
                         ("conv_bc", cbc_fin)):
            c = new_cache[key]
            new_cache[key] = c.at[idx, Bd:Bd + plan.Bp].set(
                _cache_value(val, c.dtype))
    if zd is not None:
        if plan.Sd > 1:
            raise NotImplementedError(
                "mamba decode state cannot roll back rejected drafts; "
                "speculative verify chunks are attention-only")
        B = plan.Bd
        y_x, cx_new = M.causal_conv(xd, p["conv_x"], p["conv_bx"],
                                    cache["conv_x"][idx, :Bd])
        y_bc, cbc_new = M.causal_conv(bd, p["conv_bc"], p["conv_bbc"],
                                      cache["conv_bc"][idx, :Bd])
        y_x, y_bc = jax.nn.silu(y_x[:, 0]), jax.nn.silu(y_bc[:, 0])
        xs2 = y_x.reshape(B, nh, hdm)
        b2, c2 = _expand_bc(y_bc, (B,))
        dtv = jax.nn.softplus(dd[:, 0].astype(jnp.float32)
                              + p["dt_bias"].astype(jnp.float32))
        y, h_new = M.ssd_step(cache["h"][idx, :Bd], xs2,
                              dtv.astype(xs2.dtype),
                              A, b2, c2)
        y = y + p["d_skip"].astype(y.dtype)[None, :, None] * xs2
        for key, val in (("h", h_new), ("conv_x", cx_new),
                         ("conv_bc", cbc_new)):
            c = new_cache[key]
            new_cache[key] = c.at[idx, :Bd].set(_cache_value(val, c.dtype))
        outs[2] = _gated_out(y.reshape(B, 1, di), zd, p)
    y = _merge_flat(plan, *outs)
    o = dense(y, p["out_proj"], None, lr.get("out_proj"),
              plan.ids, plan.scale_t)
    return x + o, new_cache


def _gated_out(y: jax.Array, z: jax.Array, p: Dict) -> jax.Array:
    """Mamba2 gated RMSNorm: norm(y * silu(z))."""
    return L.rms_norm(y * jax.nn.silu(z), p["mnorm"])


# ---------------------------------------------------------------------------
# FFN / MoE sublayer (token-parallel: operates on the joint stream)
# ---------------------------------------------------------------------------

def _ffn_apply(cfg: ModelConfig, pos_idx: int, p: Dict, lr: Dict,
               plan: _Plan, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    aux = jnp.zeros((), jnp.float32)
    if "ln2" not in p:
        return x, aux
    xn = L.rms_norm(x, p["ln2"], cfg.rms_eps)
    if cfg.is_moe_layer(pos_idx):
        from repro.models.moe_dist import moe_apply_auto
        y, aux = moe_apply_auto(xn, p, cfg.moe)
        if "shared" in p:
            sh, shl = p["shared"], lr.get("shared", {})
            g = dense(xn, sh["wg"], None, shl.get("wg"), plan.ids, plan.scale_t)
            u = dense(xn, sh["wu"], None, shl.get("wu"), plan.ids, plan.scale_t)
            y = y + dense(jax.nn.silu(g) * u, sh["wd"], None, shl.get("wd"),
                          plan.ids, plan.scale_t)
    else:
        g = dense(xn, p["wg"], None, lr.get("wg"), plan.ids, plan.scale_t)
        u = dense(xn, p["wu"], None, lr.get("wu"), plan.ids, plan.scale_t)
        y = dense(jax.nn.silu(g) * u, p["wd"], None, lr.get("wd"),
                  plan.ids, plan.scale_t)
    return x + y, aux


# ---------------------------------------------------------------------------
# encoder (whisper backbone; bidirectional, LoRA-free)
# ---------------------------------------------------------------------------

def encoder_forward(cfg: ModelConfig, enc_params: Dict,
                    frames: jax.Array) -> jax.Array:
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    B, F, d = frames.shape
    valid = jnp.ones((B, F), bool)
    kpos = jnp.zeros((B, F), jnp.int32)

    def body(x, p):
        xn = L.rms_norm(x, p["ln1"], cfg.rms_eps)
        flat = xn.reshape(B * F, d)
        q = (flat @ p["wq"].astype(x.dtype)).reshape(B, F, h, hd)
        k = (flat @ p["wk"].astype(x.dtype)).reshape(B, F, kv, hd)
        v = (flat @ p["wv"].astype(x.dtype)).reshape(B, F, kv, hd)
        o = L.attention(q, k, v, q_pos=kpos, k_pos=kpos, k_valid=valid,
                        causal=False)
        x = x + (o.reshape(B * F, h * hd) @ p["wo"].astype(x.dtype)
                 ).reshape(B, F, d)
        xn = L.rms_norm(x, p["ln2"], cfg.rms_eps)
        y = L.swiglu(xn.reshape(B * F, d), p["wg"], p["wu"], p["wd"])
        return x + y.reshape(B, F, d), None

    x, _ = jax.lax.scan(body, frames, enc_params["blocks"])
    return L.rms_norm(x, enc_params["final_norm"], cfg.rms_eps)


# ---------------------------------------------------------------------------
# unified forward (Algorithms 1 + 2)
# ---------------------------------------------------------------------------

def unified_forward(cfg: ModelConfig, params: Dict, batch: UnifiedBatch,
                    cache: Optional[Dict] = None, *,
                    loras: Optional[Dict] = None,
                    lora_scale: Optional[jax.Array] = None,
                    remat: bool = False, attn_chunk: int = 0,
                    return_ft_logits: bool = False,
                    act_constraint: Optional[Callable] = None) -> ModelOut:
    dtype = jnp.dtype(cfg.dtype)
    plan = _Plan(cfg, batch, lora_scale)
    if (batch.pf is not None or batch.dec is not None) and cache is None:
        raise ValueError("prefill/decode buckets require a cache")
    # int8 weight-only serving: top-level leaves dequantize here (sharded,
    # small per device); block leaves dequantize per-period inside the scan
    # so HBM holds int8 and only one layer's bf16 weights exist at a time.
    quantized = quant.has_q8(params)
    gather_specs = None
    if quantized:
        params = dict(params)
        for key in ("embed", "lm_head", "encoder"):
            if key in params:
                params[key] = quant.dequant_tree(params[key], dtype)
        gather_specs = quant.block_gather_specs(cfg)

    # encoder / modality stubs -> replace aux_embed by encoder output
    if cfg.encoder is not None:
        if batch.ft is not None and batch.ft.aux_embed is not None:
            enc = encoder_forward(cfg, params["encoder"],
                                  batch.ft.aux_embed.astype(dtype))
            plan.ft = plan.ft._replace(aux_embed=enc)
        if batch.pf is not None and batch.pf.aux_embed is not None:
            enc = encoder_forward(cfg, params["encoder"],
                                  batch.pf.aux_embed.astype(dtype))
            plan.pf = plan.pf._replace(aux_embed=enc)

    # joint embedding over the whole token stream
    toks = []
    if batch.ft is not None:
        toks.append(batch.ft.tokens.reshape(-1))
    if batch.pf is not None:
        toks.append(batch.pf.tokens.reshape(-1))
    if batch.dec is not None:
        toks.append(batch.dec.tokens.reshape(-1))
    tokens = jnp.concatenate(toks)
    x = params["embed"].astype(dtype)[tokens]                     # [T, d]

    lora_blocks = (loras["blocks"] if loras is not None
                   else tuple({} for _ in cfg.pattern))
    cache_layers = (cache["layers"] if cache is not None
                    else tuple({} for _ in cfg.pattern))

    # The cache rides in the scan CARRY, stacked over layers, rather than as
    # scan xs/ys: XLA buffer assignment then keeps ONE cache buffer alive
    # instead of double-buffering sliced-in xs against stacked-out ys.  Each
    # sublayer reads and writes its layer's part of the stacked leaves at
    # ``idx`` in place (a scatter of the step's tokens, a gather of the rows
    # it reads); slicing the layer out and writing it back would move that
    # layer's whole pool twice.  Cache writes carry no derivative
    # (``_cache_value``), so the grad step differentiates no scatter.
    def period(carry, xs):
        xx, aux_acc, cl, idx = carry
        pp, ll = xs
        if quantized:
            pp = quant.dequant_tree(pp, jnp.dtype(cfg.dtype), gather_specs)
        new_cl = []
        for pos, kind in enumerate(cfg.pattern):
            if kind == "attn":
                xx, c_out = _attn_apply(cfg, pos, pp[pos], ll[pos], plan, xx,
                                        cl[pos], idx, attn_chunk)
            else:
                xx, c_out = _mamba_apply(cfg, pp[pos], ll[pos], plan, xx,
                                         cl[pos], idx)
            xx, aux = _ffn_apply(cfg, pos, pp[pos], ll[pos], plan, xx)
            aux_acc = aux_acc + aux
            new_cl.append(c_out)
        if act_constraint is not None:
            xx = act_constraint(xx)
        return (xx, aux_acc, tuple(new_cl), idx + 1), None

    body = jax.checkpoint(period) if remat else period
    (x, aux_loss, new_layers, _), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), cache_layers,
               jnp.zeros((), jnp.int32)),
        (params["blocks"], lora_blocks))
    new_cache = {"layers": new_layers} if cache is not None else None

    x = L.rms_norm(x, params["final_norm"], cfg.rms_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(dtype)

    xf, xp, xd = plan.split(x)
    ft_loss = ft_cnt = ft_logits = pf_logits = dec_logits = None
    if xd is not None:
        # [Bd, V] for plain decode; [Bd, Sd, V] for verify chunks (one
        # next-token distribution per chunk position, the acceptance oracle)
        dec_logits = xd[:, 0] @ head if plan.Sd == 1 else xd @ head
    if xp is not None:
        last = jnp.maximum(batch.pf.length - 1, 0)
        h_last = xp[jnp.arange(plan.Bp), last]
        pf_logits = h_last @ head
    if xf is not None:
        ft = batch.ft
        logits = (xf.reshape(-1, cfg.d_model) @ head
                  ).reshape(plan.Bf, plan.Sf, -1)
        if return_ft_logits:
            ft_logits = logits
        lg = logits[:, :-1].astype(jnp.float32)
        lbl = ft.labels[:, 1:]
        valid = (lbl != -100) & ft.mask[:, 1:]
        lbl_safe = jnp.maximum(lbl, 0)
        lse = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(lg, lbl_safe[..., None], axis=-1)[..., 0]
        ce = jnp.where(valid, lse - picked, 0.0)
        ft_loss = ce.sum(axis=1)
        ft_cnt = valid.sum(axis=1).astype(jnp.float32)

    return ModelOut(ft_loss_sum=ft_loss, ft_tok_count=ft_cnt,
                    ft_logits=ft_logits, pf_logits=pf_logits,
                    dec_logits=dec_logits, cache=new_cache, aux_loss=aux_loss)
