"""The main path's Pallas kernels compile for a TPU v5e at Llama-3-8B widths
(32 query / 8 KV heads, head_dim 128, 32-token KV blocks, d_model 4096,
bf16).  Nothing runs: each kernel is lowered and compiled for a chip of a
described ``v5e:2x2`` topology, which raises what the chip's compiler would
raise — the block-tiling and VMEM rules that interpret mode never checks.
The unified step at DeepSeek-7B widths is compiled the same way, and its
optimized program is read for passes over a whole KV pool.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler's library at a time, and every worker of
a parallel run imports this file.
"""
import re

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


# -- the unified step addresses the KV pool in place --------------------------
# DeepSeek LLM 7B widths (the deepseek7b-pp3 benchmark configuration: d_model
# 4096, 32 / 32 KV heads x 128, d_ff 11008, vocab 102400) at 2 layers, LoRA
# rank 8 on q, k, v, o, gate, up, down; 32-token blocks, 32-block tables.
# step -> (pool blocks, decode rows, fine-tune rows x tokens, prefill rows x
# tokens): the saturated forward step and the co-serving grad step.
DS_LAYERS, DS_NBT = 2, 32
UNIFIED_STEPS = {"forward": (760, 40, None, None),
                 "grad": (160, 8, (2, 512), (2, 128))}
# ops that only hand a pool buffer on; ``copy`` stays until the step
# donates its cache
POOL_PASS_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast",
                 "copy", "copy-start", "copy-done"}


def _ds7b_step_args(one_chip, n_blocks, n_dec, ft, pf):
    from repro.core.lora import LoRAConfig, init_lora_bank
    from repro.models.configs import ModelConfig
    from repro.models.model import init_paged_cache
    from repro.models.schema import abstract_params, lora_targets
    from repro.models.stream import DECBatch, FTBatch, PFBatch, UnifiedBatch
    cfg = ModelConfig(name="deepseek7b-pp3", arch_type="dense",
                      n_layers=DS_LAYERS, d_model=4096, n_heads=32,
                      n_kv_heads=32, d_ff=11008, vocab=102400, head_dim=128,
                      rope_theta=1e4, rms_eps=1e-6, tie_embeddings=False,
                      dtype="bfloat16")
    lcfg = LoRAConfig(n_slots=9, r=8, alpha=16.0,
                      targets=("wq", "wk", "wv", "wo", "wg", "wu", "wd"))

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=one_chip), tree)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    bank = jax.eval_shape(lambda: init_lora_bank(
        jax.random.PRNGKey(0), lora_targets(cfg, lcfg.targets), lcfg,
        dtype=BF16))
    cache = jax.eval_shape(lambda: init_paged_cache(cfg, n_blocks, BS, n_dec))
    batch = UnifiedBatch(
        ft=None if ft is None else FTBatch(
            tokens=arr(I32, *ft), mask=arr(jnp.bool_, *ft),
            labels=arr(I32, *ft), adapter=arr(I32, ft[0]),
            weight=arr(F32, ft[0])),
        pf=None if pf is None else PFBatch(
            tokens=arr(I32, *pf), length=arr(I32, pf[0]),
            adapter=arr(I32, pf[0]), block_tables=arr(I32, pf[0], DS_NBT),
            cached_len=arr(I32, pf[0])),
        dec=DECBatch(tokens=arr(I32, n_dec), pos=arr(I32, n_dec),
                     adapter=arr(I32, n_dec),
                     block_tables=arr(I32, n_dec, DS_NBT),
                     length=arr(I32, n_dec)))
    return cfg, (on_chip(abstract_params(cfg)), on_chip(bank),
                 arr(F32, lcfg.n_slots), batch, on_chip(cache))


def _top_level_ops(hlo: str):
    """(name, shape, opcode, op_name) of every instruction outside fused
    computations of an optimized HLO module."""
    fused = set(re.findall(r"calls=%?([\w.\-]+)", hlo))
    comp, out = None, []
    for line in hlo.splitlines():
        if line and not line[0].isspace():
            head = line.split()
            comp = head[1] if head[0] == "ENTRY" else head[0]
            comp = comp.lstrip("%")
            continue
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = \w+\[([\d,]*)\]"
                     r"(?:\{[^}]*\})?\s+([\w\-]+)\(", line)
        if m and comp not in fused:
            op_name = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), m.group(2), m.group(3),
                        op_name.group(1) if op_name else ""))
    return out


@pytest.mark.parametrize("kind", sorted(UNIFIED_STEPS))
def test_unified_step_writes_pool_in_place(one_chip, kind):
    """No op of the compiled step outputs one layer's whole pool or the whole
    stacked pool, except ops that hand the buffer on and the in-place
    scatters of the step's tokens: no per-layer slice or write-back, and no
    select over the pool from differentiating a cache write."""
    from repro.core.unified import make_forward_step, make_grad_step
    n_blocks, n_dec, ft, pf = UNIFIED_STEPS[kind]
    cfg, args = _ds7b_step_args(one_chip, n_blocks, n_dec, ft, pf)
    step = (jax.jit(make_forward_step(cfg, jit=False)) if kind == "forward"
            else make_grad_step(cfg))
    hlo = step.lower(*args).compile().as_text()
    layer = f"{BS},{cfg.n_kv_heads},{cfg.hd}"
    pools = {f"{n_blocks},{layer}", f"{DS_LAYERS},{n_blocks},{layer}",
             f"{DS_LAYERS * n_blocks},{layer}"}
    on_pool = [op for op in _top_level_ops(hlo) if op[1] in pools]
    scatters = [op for op in on_pool
                if op[2] == "fusion" and op[3].endswith("/scatter")]
    assert scatters, "the step writes no token into the pool"
    bad = [op for op in on_pool
           if op[2] not in POOL_PASS_OPS and op not in scatters]
    assert not bad, bad
