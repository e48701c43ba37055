"""BGMV — batched-gather multi-LoRA matmul for the decode bucket.

Each decode token belongs to its own request and therefore its own adapter,
so segments degenerate to single tokens.  The grid runs one program per
(token, output tile); the token's adapter id arrives via scalar prefetch and
selects the A/B blocks the BlockSpec DMAs into VMEM.  This is the TPU
analogue of Punica's BGMV: throughput is DMA-bound (one [d_in, r] + [r, bo]
weight fetch per token), which is the right trade at decode batch sizes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bgmv_kernel(ids_ref, scale_ref, x_ref, a_ref, b_ref, o_ref):
    t = pl.program_id(0)
    xa = jnp.dot(x_ref[0], a_ref[0],
                 preferred_element_type=jnp.float32)        # [1, r]
    y = jnp.dot(xa, b_ref[0].astype(jnp.float32),
                preferred_element_type=jnp.float32)         # [1, bo]
    o_ref[0] = (y * scale_ref[t]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_o", "interpret"))
def bgmv(x: jax.Array, a: jax.Array, b: jax.Array, ids: jax.Array,
         scale: jax.Array, *, block_o: int = 128,
         interpret: bool = False) -> jax.Array:
    """x: [T, d_in]; a: [n, d_in, r]; b: [n, r, d_out]; ids: [T] int32
    (clipped); scale: [T] f32 (0.0 disables).  Returns [T, d_out].

    Tokens ride a unit middle axis ([T, 1, d]) so each grid step's x/out
    block is a whole (1, d) row tile — the TPU compiler's tiling rule (last
    two block dims divisible by (8, 128) or equal to the array's) then
    holds for any T."""
    T, d_in = x.shape
    n, _, r = a.shape
    d_out = b.shape[-1]
    if d_out % block_o:
        raise ValueError(f"bgmv: d_out={d_out} is not a multiple of "
                         f"block_o={block_o}")
    no = d_out // block_o

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T, no),
        in_specs=[
            pl.BlockSpec((1, 1, d_in), lambda t, j, ids, sc: (t, 0, 0)),
            pl.BlockSpec((1, d_in, r), lambda t, j, ids, sc: (ids[t], 0, 0)),
            pl.BlockSpec((1, r, block_o), lambda t, j, ids, sc: (ids[t], 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_o),
                               lambda t, j, ids, sc: (t, 0, j)),
    )
    out = pl.pallas_call(
        _bgmv_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, 1, d_out), x.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), scale.astype(jnp.float32), x[:, None], a, b)
    return out[:, 0]
