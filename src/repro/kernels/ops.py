"""jit'd dispatch wrappers around the Pallas kernels.

Kernels run compiled unless the caller passes ``interpret=True`` (the CPU
tests do); nothing here picks a mode or an implementation from the
platform, and a shape a kernel cannot take raises instead of quietly
running the ``ref.py`` oracle.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.bgmv import bgmv as _bgmv_kernel
from repro.kernels.decode_attn import decode_attention as _decode_kernel
from repro.kernels.flash_attn import flash_attention as _flash_kernel
from repro.kernels.smlm import smlm as _smlm_kernel


def smlm(x: jax.Array, a: jax.Array, b: jax.Array, ids: jax.Array,
         scale_t: Optional[jax.Array] = None, *, block_t: int = 128,
         block_o: int = 128, interpret: bool = False) -> jax.Array:
    """Segmented multi-LoRA matmul over a tile-aligned token stream.

    ``ids``/``scale_t`` are PER-TOKEN; the flow planner guarantees each
    ``block_t`` tile is adapter-uniform, so the wrapper derives per-tile
    scalars by striding.

    A ragged stream (``T % block_t != 0`` — e.g. a decode tail appended to
    the tile-aligned ft+pf segments) no longer silently falls back to the
    dense one-hot oracle for the WHOLE stream: the tile-aligned head keeps
    the fused kernel and only the sub-tile remainder goes through the
    per-token BGMV path — which is also what keeps a remainder with MIXED
    adapters (decode rows) exact, since per-token ids never get collapsed
    into a tile scalar there.
    """
    T = x.shape[0]
    n = a.shape[0]
    if b.shape[-1] % block_o:
        raise ValueError(f"smlm: d_out={b.shape[-1]} is not a multiple of "
                         f"block_o={block_o}")
    rem = T % block_t
    if rem:
        t0 = T - rem
        tail = bgmv(x[t0:], a, b, ids[t0:],
                    scale_t[t0:] if scale_t is not None else None,
                    block_o=block_o, interpret=interpret)
        if t0 == 0:
            return tail
        head = smlm(x[:t0], a, b, ids[:t0],
                    scale_t[:t0] if scale_t is not None else None,
                    block_t=block_t, block_o=block_o, interpret=interpret)
        return jnp.concatenate([head, tail], axis=0)
    tile_ids = ids[::block_t]
    valid = (tile_ids >= 0) & (tile_ids < n)
    if scale_t is None:
        tile_scale = valid.astype(jnp.float32)
    else:
        tile_scale = jnp.where(valid, scale_t[::block_t], 0.0)
    tile_ids = jnp.clip(tile_ids, 0, n - 1)
    return _smlm_kernel(x, a, b, tile_ids, tile_scale,
                        block_t=block_t, block_o=block_o, interpret=interpret)


def bgmv(x: jax.Array, a: jax.Array, b: jax.Array, ids: jax.Array,
         scale_t: Optional[jax.Array] = None, *, block_o: int = 128,
         interpret: bool = False) -> jax.Array:
    """Per-token multi-LoRA matmul (decode bucket)."""
    T = x.shape[0]
    n = a.shape[0]
    valid = (ids >= 0) & (ids < n)
    if scale_t is None:
        scale = valid.astype(jnp.float32)
    else:
        scale = jnp.where(valid, scale_t, 0.0)
    ids = jnp.clip(ids, 0, n - 1)
    return _bgmv_kernel(x, a, b, ids, scale, block_o=block_o,
                        interpret=interpret)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    lengths: jax.Array, *, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """Flash attention (prefill)."""
    return _flash_kernel(q, k, v, lengths, causal=causal, block_q=block_q,
                         block_k=block_k, interpret=interpret)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos: jax.Array, *, window: int = 0,
                     block_k: int = 512,
                     interpret: bool = False) -> jax.Array:
    """Batch-decode attention (one token per request over a long cache)."""
    return _decode_kernel(q, k, v, pos, block_k=block_k, window=window,
                          interpret=interpret)
