"""Batch-decode attention Pallas kernel — the TPU analogue of FlashInfer's
batch-decode path (one query token per request over a long KV cache).

Grid (B, h, nk): the KV cache streams through VMEM in blocks along the
sequence axis with an online softmax; per-request valid lengths arrive via
scalar prefetch.  GQA maps query head -> kv head in the BlockSpec index map,
so the cache is read once per kv head group.  Optional rolling-buffer
support: positions are reconstructed from ``lengths`` exactly like the model
does (slot j holds absolute position j + W*floor((pos - j)/W)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, block_k: int, nk: int, scale: float,
                   window: int, sc: int):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0, :]                                         # [hd]
    k = k_ref[0, :, 0, :]                                      # [bk, hd]
    v = v_ref[0, :, 0, :]
    pos = pos_ref[b]                                           # query position
    j = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)[0]
    # absolute position held by rolling slot j (== j for a linear cache)
    if window > 0:
        k_pos = j + sc * jax.lax.div(pos - j, sc)
    else:
        k_pos = j
    mask = (k_pos <= pos) & (k_pos >= 0)
    if window > 0:
        mask = mask & (pos - k_pos < window)
    s = jnp.dot(k, q, preferred_element_type=jnp.float32) * scale  # [bk]
    s = jnp.where(mask, s, NEG_INF)
    m_prev, l_prev = m_ref[0], l_ref[0]
    m_new = jnp.maximum(m_prev, s.max())
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
    l_ref[0] = l_prev * corr + p.sum()
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)[None]
    m_ref[0] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[0], 1e-30)
        o_ref[0, 0, :] = (acc_ref[0] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "window",
                                              "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos: jax.Array, *, block_k: int = 512, window: int = 0,
                     interpret: bool = False) -> jax.Array:
    """q: [B, h, hd] (the current token's query);
    k/v: [B, S, g, hd] cache (rolling buffer when window > 0);
    pos: [B] int32 current positions (cache holds <= pos tokens).
    Returns [B, h, hd]."""
    B, h, hd = q.shape
    S, g = k.shape[1], k.shape[2]
    m = h // g
    pad = (-S) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nk = k.shape[1] // block_k
    scale = hd ** -0.5

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, h, nk),
        in_specs=[
            pl.BlockSpec((1, 1, hd), lambda b, hq, ik, P_: (b, hq, 0)),
            pl.BlockSpec((1, block_k, 1, hd),
                         lambda b, hq, ik, P_: (b, ik, hq // m, 0)),
            pl.BlockSpec((1, block_k, 1, hd),
                         lambda b, hq, ik, P_: (b, ik, hq // m, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda b, hq, ik, P_: (b, hq, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, hd), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
        ],
    )
    kern = functools.partial(_decode_kernel, block_k=block_k, nk=nk,
                             scale=scale, window=window, sc=S)
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, h, hd), q.dtype),
        interpret=interpret,
    )(pos.astype(jnp.int32), q, k, v)


# ---------------------------------------------------------------------------
# paged attention: the KV cache is a flat block pool, each request's blocks
# are gathered through a scalar-prefetched block table in the BlockSpec
# index map — the DMA engine walks the table, the kernel never sees the
# indirection.  ONE kernel serves every paged query shape: batch decode is a
# one-token chunk, speculative verify a (1 + k)-token chunk (k drafts
# checked in one cache pass instead of k decodes), and suffix-only prefill
# a chunk tiled along the query axis — all attend to keys
# ``0 .. start + len - 1`` with positional causality.  The chunk's own K/V
# are written to the pool before the call, so the kernel never tells
# cached prefix keys from fresh ones: a shared prefix is read, not
# recomputed.
#
# TPU layout (the Mosaic compiler wants the last two block dims divisible by
# (8, 128) or equal to the array's): the pool [nb, bs, g, hd] is viewed as
# [nb, bs, g*hd] (a free reshape) so one KV head of one block is a (bs, hd)
# tile, and the queries are regrouped per KV head as [B, g, Sq*m, hd] (row
# ``t*m + i`` is token t, query head i of the group) so each grid step
# scores the whole GQA group against the block it streams: the block is
# DMA'd once per KV head, not once per query head.
# ---------------------------------------------------------------------------

# lane width of the per-row softmax statistics: m and l live lane-replicated
# in [rows, LANES] f32 scratch (a (rows, 1) scratch would not tile)
LANES = 128


def group_queries(q: jax.Array, g: int, sq_pad: int) -> jax.Array:
    """[B, Sq, h, hd] -> [B, g, sq_pad*m, hd]: GQA group-major query rows
    (token-major within a group), zero-padded along the chunk."""
    B, Sq, h, hd = q.shape
    m = h // g
    q = q.reshape(B, Sq, g, m, hd).transpose(0, 2, 1, 3, 4)
    if sq_pad > Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - Sq), (0, 0), (0, 0)))
    return q.reshape(B, g, sq_pad * m, hd)


def ungroup_queries(o: jax.Array, h: int, sq: int) -> jax.Array:
    """Inverse of ``group_queries``: [..., g, sq_pad*m, hd] ->
    [..., sq, h, hd] over any leading axes."""
    *lead, g, rows, hd = o.shape
    m = h // g
    o = o.reshape(*lead, g, rows // m, m, hd)
    o = jnp.moveaxis(o, -4, -3)                  # [..., sq_pad, g, m, hd]
    return o.reshape(*lead, rows // m, h, hd)[..., :sq, :, :]


def online_softmax_step(q, k, v, mask, scale, acc_ref, m_ref, l_ref):
    """One block of the streaming softmax: score ``q`` [rows, hd] against
    ``k``/``v`` [bs, hd] under ``mask`` [rows, bs] and fold the block into
    the running (acc [rows, hd], m/l [rows, LANES]) scratch."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new[:, :1]), 0.0)
    corr = jnp.exp(jnp.minimum(m_prev - m_new, 0.0))
    l_ref[...] = l_prev * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr[:, :1] + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def chunk_mask(rows: int, bs: int, m: int, ib, q0, start, ln):
    """[rows, bs] mask of key slot ``ib*bs + j`` for query row ``r`` (token
    ``q0 + r // m`` of the chunk): causal over absolute positions, and
    valid through the chunk's written length ``start + ln``.  Null-padded
    table entries lie past ``start + ln`` and fail it too."""
    j = ib * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
    t = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 0),
                    jnp.int32(m))
    qi = start + q0 + t
    return (j <= qi) & (j < start + ln)


def _paged_attn_kernel(tbl_ref, start_ref, len_ref, q_ref, k_ref, v_ref,
                       o_ref, acc_ref, m_ref, l_ref, *, bs: int, nbt: int,
                       m: int, block_q: int, scale: float):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ib = pl.program_id(3)

    @pl.when(ib == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    rows = block_q * m
    mask = chunk_mask(rows, bs, m, ib, iq * block_q, start_ref[b],
                      len_ref[b])
    online_softmax_step(q_ref[0, 0], k_ref[0], v_ref[0], mask, scale,
                        acc_ref, m_ref, l_ref)

    @pl.when(ib == nbt - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...][:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def paged_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,  # reprolint: oracle=paged_prefill_ref
                    block_tables: jax.Array, start: jax.Array,
                    lens: jax.Array, *, block_q: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Chunked-query attention over a paged KV pool.

    q: [B, Sq, h, hd] chunk queries at absolute positions
        ``start .. start + Sq - 1`` (already roped);
    k_pool/v_pool: [n_blocks, bs, g, hd] flat block pool — the chunk's own
        K/V must already be written at ``start .. start + lens - 1``;
    block_tables: [B, nbt] int32 per-request block ids, null-padded
        (padding entries are clamped to block 0 and masked out);
    start: [B] int32 tokens already in the cache before the chunk;
    lens: [B] int32 valid chunk lengths (0 = padding row -> zeros; query
        rows past ``lens`` produce values the caller never reads).
    Returns [B, Sq, h, hd].

    Grid (B, g, nq, nbt): for a fixed (request, KV head, query tile) the
    block walk is innermost, so the VMEM accumulator carries the online
    softmax across the table; each step DMAs exactly one (bs, hd) tile of
    one KV head and scores the tile's whole query group against it.
    """
    B, Sq, h, hd = q.shape
    nb, bs, g = k_pool.shape[:3]
    m = h // g
    nbt = block_tables.shape[1]
    tbl = jnp.maximum(block_tables.astype(jnp.int32), 0)
    bq = min(block_q, max(Sq, 1))
    nq = -(-Sq // bq)
    qg = group_queries(q, g, nq * bq)
    kp = k_pool.reshape(nb, bs, g * hd)
    vp = v_pool.reshape(nb, bs, g * hd)
    rows = bq * m

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, g, nq, nbt),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd),
                         lambda b, kh, iq, ib, T_, S_, L_: (b, kh, iq, 0)),
            pl.BlockSpec((1, bs, hd),
                         lambda b, kh, iq, ib, T_, S_, L_:
                         (T_[b, ib], 0, kh)),
            pl.BlockSpec((1, bs, hd),
                         lambda b, kh, iq, ib, T_, S_, L_:
                         (T_[b, ib], 0, kh)),
        ],
        out_specs=pl.BlockSpec((1, 1, rows, hd),
                               lambda b, kh, iq, ib, T_, S_, L_:
                               (b, kh, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
        ],
    )
    kern = functools.partial(_paged_attn_kernel, bs=bs, nbt=nbt, m=m,
                             block_q=bq, scale=hd ** -0.5)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        interpret=interpret,
    )(tbl, start.astype(jnp.int32), lens.astype(jnp.int32), qg, kp, vp)
    return ungroup_queries(out, h, Sq)
