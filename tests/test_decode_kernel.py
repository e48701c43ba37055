"""Batch-decode attention Pallas kernel vs the model's decode oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attn import decode_attention, paged_attention
from repro.kernels.ref import (decode_attention_ref, paged_decode_ref,
                               paged_verify_ref)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,h,g,hd,S,bk", [
    (2, 4, 4, 8, 16, 8),       # MHA
    (3, 8, 2, 16, 40, 8),      # GQA, ragged length -> padding path
    (1, 8, 8, 32, 64, 16),
])
def test_decode_linear_cache(dtype, B, h, g, hd, S, bk):
    ks = jax.random.split(jax.random.PRNGKey(B * S), 4)
    q = jax.random.normal(ks[0], (B, h, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, g, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, g, hd)).astype(dtype)
    pos = jax.random.randint(ks[3], (B,), 0, S)
    y = decode_attention(q, k, v, pos, block_k=bk, interpret=True)
    yr = decode_attention_ref(q, k, v, pos)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("pos_val", [3, 15, 16, 47, 1000])
def test_decode_rolling_window(pos_val):
    """Rolling-buffer cache: slot->absolute-position reconstruction must
    match the model's _dec_cache_pos for positions below and above W."""
    B, h, g, hd, W = 2, 4, 2, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(pos_val), 3)
    q = jax.random.normal(ks[0], (B, h, hd))
    k = jax.random.normal(ks[1], (B, W, g, hd))
    v = jax.random.normal(ks[2], (B, W, g, hd))
    pos = jnp.array([pos_val, max(pos_val - 2, 0)])
    y = decode_attention(q, k, v, pos, block_k=8, window=W, interpret=True)
    yr = decode_attention_ref(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-5, atol=3e-5)


def _paged_setup(B, g, hd, bs, nbt, n_blocks, pos, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    k_pool = jax.random.normal(ks[0], (n_blocks, bs, g, hd))
    v_pool = jax.random.normal(ks[1], (n_blocks, bs, g, hd))
    rng = np.random.default_rng(seed)
    tables = np.zeros((B, nbt), np.int32)
    for b in range(B):
        need = pos[b] // bs + 1
        tables[b, :need] = rng.choice(np.arange(1, n_blocks), size=need,
                                      replace=False)
    return k_pool, v_pool, jnp.asarray(tables), ks[2]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,h,g,hd,bs,nbt", [
    (2, 4, 4, 8, 8, 3),        # MHA
    (3, 8, 2, 16, 8, 5),       # GQA, ragged positions
    (1, 8, 8, 32, 16, 4),
])
def test_paged_decode_kernel_matches_ref(dtype, B, h, g, hd, bs, nbt):
    """Block-table Pallas kernel == gather-then-attend oracle, with scattered
    non-contiguous blocks and null-padded tables."""
    pos = np.minimum(np.arange(B) * 7 + 3, nbt * bs - 1)
    k_pool, v_pool, tables, kq = _paged_setup(B, g, hd, bs, nbt,
                                              nbt * B + 2, pos)
    q = jax.random.normal(kq, (B, h, hd)).astype(dtype)
    k_pool, v_pool = k_pool.astype(dtype), v_pool.astype(dtype)
    posj = jnp.asarray(pos, jnp.int32)
    y = paged_attention(q[:, None], k_pool, v_pool, tables, posj,
                        jnp.ones((B,), jnp.int32), interpret=True)[:, 0]
    yr = paged_decode_ref(q, k_pool, v_pool, tables, posj)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("B,h,g,hd,bs,nbt,Sq", [
    (2, 4, 4, 8, 8, 3, 4),     # MHA
    (3, 8, 2, 16, 8, 5, 5),    # GQA, ragged chunk lengths
    (1, 8, 8, 32, 16, 4, 2),
])
def test_paged_verify_kernel_matches_ref(B, h, g, hd, bs, nbt, Sq):
    """Speculative verify attention (chunked query over block tables) ==
    gather-then-attend oracle, including padding rows (len 0) and partially
    filled chunks."""
    ks = jax.random.split(jax.random.PRNGKey(B * Sq), 3)
    n_blocks = nbt * B + 2
    k_pool = jax.random.normal(ks[0], (n_blocks, bs, g, hd))
    v_pool = jax.random.normal(ks[1], (n_blocks, bs, g, hd))
    rng = np.random.default_rng(B)
    pos = np.minimum(np.arange(B) * 5 + 2, nbt * bs - Sq - 1)
    lens = rng.integers(0, Sq + 1, B)
    tables = np.zeros((B, nbt), np.int32)
    for b in range(B):
        need = (pos[b] + Sq) // bs + 1
        tables[b, :need] = rng.choice(np.arange(1, n_blocks), size=need,
                                      replace=False)
    q = jax.random.normal(ks[2], (B, Sq, h, hd))
    posj = jnp.asarray(pos, jnp.int32)
    lensj = jnp.asarray(lens, jnp.int32)
    tj = jnp.asarray(tables)
    y = paged_attention(q, k_pool, v_pool, tj, posj, lensj, interpret=True)
    yr = paged_verify_ref(q, k_pool, v_pool, tj, posj, lensj)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=3e-5, atol=3e-5)


def test_model_decode_bucket_kernel_flag(monkeypatch):
    """REPRO_PAGED_ATTN_KERNEL wires kernels.decode_attn into the model's
    paged decode bucket (ROADMAP item): logits must match the jnp
    gather-view reference path."""
    from repro.configs import get_reduced
    from repro.models.model import init_paged_cache, unified_forward
    from repro.models.schema import init_params
    from repro.models.stream import DECBatch, PFBatch, UnifiedBatch

    cfg = get_reduced("llama3-8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                              cfg.vocab)
    base = jnp.full((B,), -1)
    tbl = jnp.asarray(np.array([[3, 1, 7, 5], [2, 6, 4, 8]], np.int32))

    def drive():
        cache = init_paged_cache(cfg, 9, 8, B)
        pf = PFBatch(tokens=toks[:, :S], length=jnp.full((B,), S),
                     adapter=base, block_tables=tbl)
        cache = unified_forward(cfg, params, UnifiedBatch(pf=pf),
                                cache=cache).cache
        dec = DECBatch(tokens=toks[:, S], pos=jnp.full((B,), S),
                       adapter=base, block_tables=tbl)
        return np.asarray(unified_forward(cfg, params, UnifiedBatch(dec=dec),
                                          cache=cache).dec_logits)

    monkeypatch.delenv("REPRO_PAGED_ATTN_KERNEL", raising=False)
    ref = drive()
    monkeypatch.setenv("REPRO_PAGED_ATTN_KERNEL", "interpret")
    got = drive()
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_model_verify_bucket_kernel_flag(monkeypatch):
    """REPRO_PAGED_ATTN_KERNEL must also drive the VERIFY bucket (multi-token
    chunks) through kernels.decode_attn — previously only prefill and
    one-token decode dispatched to Pallas and verify silently fell back to
    the jnp gather view.  Valid rows only: padding slots are never read."""
    from repro.configs import get_reduced
    from repro.models.model import init_paged_cache, unified_forward
    from repro.models.schema import init_params
    from repro.models.stream import DECBatch, PFBatch, UnifiedBatch

    cfg = get_reduced("llama3-8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S, k = 2, 10, 3
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + k + 1), 0,
                              cfg.vocab)
    base = jnp.full((B,), -1)
    tbl = jnp.asarray(np.array([[3, 1, 7, 5], [2, 6, 4, 8]], np.int32))
    lens = np.array([k + 1, k])

    def drive():
        cache = init_paged_cache(cfg, 9, 8, B)
        pf = PFBatch(tokens=toks[:, :S], length=jnp.full((B,), S),
                     adapter=base, block_tables=tbl)
        cache = unified_forward(cfg, params, UnifiedBatch(pf=pf),
                                cache=cache).cache
        dec = DECBatch(tokens=toks[:, S:S + k + 1], pos=jnp.full((B,), S),
                       adapter=base, block_tables=tbl,
                       length=jnp.asarray(lens, jnp.int32))
        return np.asarray(unified_forward(cfg, params, UnifiedBatch(dec=dec),
                                          cache=cache).dec_logits)

    monkeypatch.delenv("REPRO_PAGED_ATTN_KERNEL", raising=False)
    ref = drive()
    monkeypatch.setenv("REPRO_PAGED_ATTN_KERNEL", "interpret")
    got = drive()
    valid = lens[:, None] > np.arange(k + 1)[None, :]
    np.testing.assert_allclose(got[valid], ref[valid], rtol=2e-4, atol=2e-4)


def test_paged_kernel_matches_dense_kernel():
    """The paged path and the dense path are the same attention: materialize
    each request's blocks contiguously and the dense kernel must agree."""
    B, h, g, hd, bs, nbt = 2, 4, 2, 16, 8, 4
    pos = np.array([13, 30])
    k_pool, v_pool, tables, kq = _paged_setup(B, g, hd, bs, nbt, 16, pos)
    q = jax.random.normal(kq, (B, h, hd))
    posj = jnp.asarray(pos, jnp.int32)
    y = paged_attention(q[:, None], k_pool, v_pool, tables, posj,
                        jnp.ones((B,), jnp.int32), interpret=True)[:, 0]
    tn = np.asarray(tables)
    kd = np.asarray(k_pool)[tn].reshape(B, nbt * bs, g, hd)
    vd = np.asarray(v_pool)[tn].reshape(B, nbt * bs, g, hd)
    yd = decode_attention(q, jnp.asarray(kd), jnp.asarray(vd), posj,
                          block_k=8, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yd),
                               rtol=2e-5, atol=2e-5)
