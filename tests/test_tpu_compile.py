"""The main path's Pallas kernels compile for a TPU v5e at Llama-3-8B widths
(32 query / 8 KV heads, head_dim 128, 32-token KV blocks, d_model 4096,
bf16).  Nothing runs: each kernel is lowered and compiled for a chip of a
described ``v5e:2x2`` topology, which raises what the chip's compiler would
raise — the block-tiling and VMEM rules that interpret mode never checks.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler's library at a time, and every worker of
a parallel run imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.decode_attn import paged_attention
from repro.kernels.splitk import paged_verify_attention_splitk

H, G, HD, BS = 32, 8, 128, 32           # query heads, KV heads, head_dim, block
D, R, SLOTS = 4096, 8, 4                # d_model, LoRA rank, adapter slots
NB, NBT, B = 257, 32, 8                 # pool blocks, table width, decode rows
BF16, I32, F32 = jnp.bfloat16, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A single-chip sharding, with the persistent compilation cache off
    while this module's compiles run (an entry written for a described chip
    cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _pool():
    return (NB, BS, G, HD), BF16


# name -> (function, [(shape, dtype) per argument])
KERNELS = {
    # a one-token decode chunk, a 5-token speculative verify chunk and a
    # 256-token suffix prefill (two 128-query tiles) through the one paged
    # walk
    "paged_decode": (paged_attention,
                     [((B, 1, H, HD), BF16), _pool(), _pool(),
                      ((B, NBT), I32), ((B,), I32), ((B,), I32)]),
    "paged_verify": (paged_attention,
                     [((B, 5, H, HD), BF16), _pool(), _pool(),
                      ((B, NBT), I32), ((B,), I32), ((B,), I32)]),
    "paged_prefill": (paged_attention,
                      [((2, 256, H, HD), BF16), _pool(), _pool(),
                       ((2, NBT), I32), ((2,), I32), ((2,), I32)]),
    "paged_decode_splitk": (
        lambda q, k, v, t, p, n: paged_verify_attention_splitk(
            q, k, v, t, p, n, num_splits=4),
        [((B, 1, H, HD), BF16), _pool(), _pool(), ((B, NBT), I32),
         ((B,), I32), ((B,), I32)]),
    "paged_verify_splitk": (
        lambda q, k, v, t, p, n: paged_verify_attention_splitk(
            q, k, v, t, p, n, num_splits=3),
        [((B, 5, H, HD), BF16), _pool(), _pool(), ((B, NBT), I32),
         ((B,), I32), ((B,), I32)]),
    # 264 tokens: two adapter-uniform 128-token SMLM tiles and an
    # 8-token tail that goes through BGMV
    "smlm": (ops.smlm,
             [((264, D), BF16), ((SLOTS, D, R), F32), ((SLOTS, R, D), F32),
              ((264,), I32), ((264,), F32)]),
    "bgmv": (ops.bgmv,
             [((B, D), BF16), ((SLOTS, D, R), F32), ((SLOTS, R, D), F32),
              ((B,), I32), ((B,), F32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = KERNELS[name]
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
