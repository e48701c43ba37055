"""Flash-decoding split-K paged attention — partition + LSE-merge.

The sequential paged kernel (``decode_attn.paged_attention``) walks a
request's whole block table along ONE grid axis: grid ``(B, g, nq, nbt)``
with the online-softmax accumulator carried in VMEM scratch across the walk.
At decode batch sizes that serializes the walk — a single long-context
request occupies ``B*g`` grid cells no matter how many blocks it spans.

Flash-decoding (lite_llama's ``flash_decoding``/``softmax_split``, the
FlashInfer batch-decode design) adds the missing degree of parallelism:
partition the block-table walk into ``num_splits`` INDEPENDENT grid cells —
grid ``(B, g, num_splits, npb)`` with ``npb = ceil(nbt / num_splits)`` —
each producing a partial ``(acc, m, l)`` triple via the same online softmax,
then merge the partials with a numerically-stable log-sum-exp combine.  The
merge is a tiny jnp epilogue (`lse_merge`): for the [B, h] outputs it is a
reduction over ``num_splits`` fp32 triples, negligible next to the walk.

One kernel serves both latency-critical short-query paths: decode is the
``Sq == 1`` special case of verify (a one-token chunk with ``lens == 1``).
Splits that see only masked keys (table padding, or a padding row with
``lens == 0``) emit ``(0, -inf, 0)`` partials which the merge discards —
an empty partial cannot poison the combine.

Tile/split choices come from ``kernels.autotune`` (per-shape table with a
deterministic heuristic fallback); callers pass the chosen ``num_splits``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attn import (LANES, NEG_INF, chunk_mask,
                                       group_queries, online_softmax_step,
                                       ungroup_queries)


def lse_merge(o_part: jax.Array, m_part: jax.Array,
              l_part: jax.Array) -> jax.Array:
    """Numerically-stable combine of split-K softmax partials.

    o_part: [B, ns, Sq, h, hd] fp32 UN-normalized accumulators
        (``sum_j exp(s_j - m) v_j`` per split, with ``m`` that split's max);
    m_part: [B, ns, Sq, h] fp32 per-split score maxima (``-inf`` when the
        split saw no valid key);
    l_part: [B, ns, Sq, h] fp32 per-split softmax denominators.
    Returns [B, Sq, h, hd] fp32 — the same value a single-pass online
    softmax over the concatenated splits produces (up to fp32 rounding).

    Empty splits are inert by construction: ``m = -inf`` gives weight
    ``exp(min(m - m_max, 0))`` of either 0 (some split was non-empty) or 1
    with ``l = 0`` (ALL empty), so the output degenerates to zeros exactly
    like the sequential kernels' all-masked finalize.
    """
    m_max = jnp.max(m_part, axis=1, keepdims=True)             # [B,1,Sq,h]
    w = jnp.exp(jnp.minimum(m_part - m_max, 0.0))              # [B,ns,Sq,h]
    l_tot = jnp.sum(l_part * w, axis=1)                        # [B,Sq,h]
    o = jnp.sum(o_part * w[..., None], axis=1)                 # [B,Sq,h,hd]
    return o / jnp.maximum(l_tot, 1e-30)[..., None]


def _splitk_kernel(tbl_ref, pos_ref, len_ref, q_ref, k_ref, v_ref,
                   o_ref, m_ref, l_ref, acc_ref, ms_ref, ls_ref, *,
                   bs: int, npb: int, m: int, rows: int, scale: float):
    b = pl.program_id(0)
    s = pl.program_id(2)
    jb = pl.program_id(3)

    @pl.when(jb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ms_ref[...] = jnp.full_like(ms_ref, NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)

    # global index into the padded table; padded entries (ib >= nbt) land
    # beyond pos + ln and are masked like any null-padded entry
    ib = s * npb + jb
    mask = chunk_mask(rows, bs, m, ib, 0, pos_ref[b], len_ref[b])
    online_softmax_step(q_ref[0, 0], k_ref[0], v_ref[0], mask, scale,
                        acc_ref, ms_ref, ls_ref)

    @pl.when(jb == npb - 1)
    def _emit():
        # UN-normalized partial: the LSE merge owns the division
        o_ref[0, 0, 0] = acc_ref[...]
        m_ref[0, 0, 0] = ms_ref[...]
        l_ref[0, 0, 0] = ls_ref[...]


@functools.partial(jax.jit, static_argnames=("num_splits", "interpret"))
def paged_verify_attention_splitk(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, block_tables: jax.Array,
                                  pos: jax.Array, lens: jax.Array, *,
                                  num_splits: int = 4,
                                  interpret: bool = False) -> jax.Array:
    """Split-K speculative verify attention over a paged KV pool.

    Same contract and TPU layout as ``decode_attn.paged_attention`` with
    ``start = pos`` (q: [B, Sq, h, hd]; k_pool/v_pool: [n_blocks, bs, g,
    hd]; block_tables: [B, nbt] null-padded; pos/lens: [B]; returns [B, Sq,
    h, hd]), but the block walk is partitioned across ``num_splits``
    independent grid cells per (b, kv head) and the partial ``(acc, m, l)``
    triples are combined by ``lse_merge``.

    Grid (B, g, ns, npb): the inner axis walks ``npb = ceil(nbt / ns)``
    consecutive table entries of one split; the split axis is parallel —
    nothing is carried across it.  ``num_splits`` may exceed the occupied
    table span: surplus splits read only null-padded entries and emit empty
    partials that the merge ignores.
    """
    B, Sq, h, hd = q.shape
    nb, bs, g = k_pool.shape[:3]
    m = h // g
    rows = Sq * m
    nbt = block_tables.shape[1]
    ns = max(1, int(num_splits))
    npb = -(-nbt // ns)
    tbl = jnp.maximum(block_tables.astype(jnp.int32), 0)
    if ns * npb > nbt:                   # pad with null blocks (masked out)
        tbl = jnp.pad(tbl, ((0, 0), (0, ns * npb - nbt)))
    qg = group_queries(q, g, Sq)
    kp = k_pool.reshape(nb, bs, g * hd)
    vp = v_pool.reshape(nb, bs, g * hd)

    def part(width):
        return pl.BlockSpec((1, 1, 1, rows, width),
                            lambda b, kh, s, jb, T_, P_, L_: (b, s, kh, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, g, ns, npb),
        in_specs=[
            pl.BlockSpec((1, 1, rows, hd),
                         lambda b, kh, s, jb, T_, P_, L_: (b, kh, 0, 0)),
            pl.BlockSpec((1, bs, hd),
                         lambda b, kh, s, jb, T_, P_, L_:
                         (T_[b, s * npb + jb], 0, kh)),
            pl.BlockSpec((1, bs, hd),
                         lambda b, kh, s, jb, T_, P_, L_:
                         (T_[b, s * npb + jb], 0, kh)),
        ],
        out_specs=[part(hd), part(LANES), part(LANES)],
        scratch_shapes=[
            pltpu.VMEM((rows, hd), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.float32),
        ],
    )
    kern = functools.partial(_splitk_kernel, bs=bs, npb=npb, m=m, rows=rows,
                             scale=hd ** -0.5)
    o_part, m_part, l_part = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, ns, g, rows, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, ns, g, rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, ns, g, rows, LANES), jnp.float32),
        ],
        interpret=interpret,
    )(tbl, pos.astype(jnp.int32), lens.astype(jnp.int32), qg, kp, vp)
    # back to the merge's [B, ns, Sq, h(, hd)] layout (lane 0 of the stats)
    o_part = ungroup_queries(o_part, h, Sq)
    m_part = ungroup_queries(m_part[..., :1], h, Sq)[..., 0]
    l_part = ungroup_queries(l_part[..., :1], h, Sq)[..., 0]
    return lse_merge(o_part, m_part, l_part).astype(q.dtype)
