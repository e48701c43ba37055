"""Prefix-aware suffix-only prefill + chunked prefill: kernel vs oracle,
model-level equivalence (standard attention AND MLA, chunked and unchunked),
and engine end-to-end — hash-deduped / chunked runs must emit byte-identical
tokens to full-prompt prefill, with the per-tick prefill budget bounding
every step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.lora import LoRAConfig
from repro.core.virtualization import AdapterStore, MixedLoraModel
from repro.data import datasets
from repro.kernels.decode_attn import paged_attention
from repro.kernels.ref import paged_prefill_ref
from repro.models.model import init_paged_cache, unified_forward
from repro.models.schema import init_params
from repro.models.stream import PFBatch, UnifiedBatch
from repro.serving.engine import EngineConfig, UnifiedEngine
from repro.serving.request import Request
from repro.spec import SpecConfig
from repro.training.trainer import MixedLoraTrainer, TrainerConfig

LCFG = LoRAConfig(n_slots=4, r=4)


# ------------------------------------------------------------------ kernel
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,h,g,hd,bs,nbt,Sq,bq", [
    (2, 4, 4, 8, 8, 4, 12, 4),     # MHA, multi-tile query walk
    (3, 8, 2, 16, 8, 6, 7, 8),     # GQA, ragged query pad
    (1, 8, 8, 32, 16, 5, 33, 16),  # wide suffix, several tiles
])
def test_paged_prefill_kernel_matches_ref(dtype, B, h, g, hd, bs, nbt, Sq,
                                          bq):
    """Query-tiled block-table prefill kernel == gather-then-attend oracle,
    with non-contiguous blocks, per-row cached prefixes, ragged suffix
    lengths, and padding rows (seg 0)."""
    rng = np.random.default_rng(B * Sq + bq)
    ks = jax.random.split(jax.random.PRNGKey(B * Sq), 3)
    n_blocks = nbt * B + 2
    k_pool = jax.random.normal(ks[0], (n_blocks, bs, g, hd)).astype(dtype)
    v_pool = jax.random.normal(ks[1], (n_blocks, bs, g, hd)).astype(dtype)
    cached = rng.integers(0, nbt * bs - Sq, B)
    seg = np.concatenate([[0], rng.integers(1, Sq + 1, B - 1)]) \
        if B > 1 else rng.integers(1, Sq + 1, B)
    tables = np.zeros((B, nbt), np.int32)
    for b in range(B):
        need = max((cached[b] + Sq - 1) // bs + 1, 1)
        tables[b, :need] = rng.choice(np.arange(1, n_blocks), size=need,
                                      replace=False)
    q = jax.random.normal(ks[2], (B, Sq, h, hd)).astype(dtype)
    cj = jnp.asarray(cached, jnp.int32)
    sj = jnp.asarray(seg, jnp.int32)
    tj = jnp.asarray(tables)
    y = np.asarray(paged_attention(q, k_pool, v_pool, tj, cj, sj,
                                   block_q=bq, interpret=True), np.float32)
    yr = np.asarray(paged_prefill_ref(q, k_pool, v_pool, tj, cj, sj),
                    np.float32)
    tol = 3e-5 if dtype == jnp.float32 else 5e-2
    for b in range(B):  # rows past seg are padding (garbage in both paths)
        np.testing.assert_allclose(y[b, :seg[b]], yr[b, :seg[b]],
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("Sq,bq", [(1, 128), (4, 128), (20, 8)])
def test_paged_attention_serves_every_chunk_shape(Sq, bq):
    """The one paged walk behind decode (Sq 1), verify (Sq 1 + k) and
    query-tiled prefill == the chunk oracle, at a GQA group of 4."""
    B, h, g, hd, bs, nbt = 2, 8, 2, 16, 8, 6
    ks = jax.random.split(jax.random.PRNGKey(Sq), 3)
    n_blocks = nbt * B + 1
    k_pool = jax.random.normal(ks[0], (n_blocks, bs, g, hd))
    v_pool = jax.random.normal(ks[1], (n_blocks, bs, g, hd))
    tables = jnp.arange(1, n_blocks, dtype=jnp.int32)[::-1].reshape(B, nbt)
    start = jnp.asarray([nbt * bs - Sq - 3, 5], jnp.int32)
    lens = jnp.asarray([Sq, max(Sq - 1, 1)], jnp.int32)
    q = jax.random.normal(ks[2], (B, Sq, h, hd))
    y = np.asarray(paged_attention(q, k_pool, v_pool, tables, start, lens,
                                   block_q=bq, interpret=True))
    yr = np.asarray(paged_prefill_ref(q, k_pool, v_pool, tables, start,
                                      lens))
    for b in range(B):
        n = int(lens[b])
        np.testing.assert_allclose(y[b, :n], yr[b, :n], rtol=3e-5, atol=3e-5)


# ----------------------------------------------------- model equivalence
def _split_points(S, n_chunks, rng):
    cuts = sorted(rng.choice(np.arange(1, S), size=n_chunks - 1,
                             replace=False)) if n_chunks > 1 else []
    return [0] + list(int(c) for c in cuts) + [S]


@pytest.mark.parametrize("arch", ["llama3-8b", "deepseek-v2-236b"])
@pytest.mark.parametrize("n_chunks", [2, 3])
def test_chunked_suffix_prefill_matches_full(arch, n_chunks):
    """Driving a prompt through ``n_chunks`` suffix-only prefill calls
    (cached_len = tokens already written, arbitrary non-aligned chunk
    boundaries) must reproduce the full-prompt prefill logits and leave the
    block pool byte-identical — for standard attention AND MLA."""
    cfg = get_reduced(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 23
    rng = np.random.default_rng(n_chunks)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    base = jnp.full((B,), -1)
    tbl = jnp.asarray(np.array([[3, 1, 7, 5], [2, 6, 4, 8]], np.int32))

    cache = init_paged_cache(cfg, 9, 8, B)
    pf = PFBatch(tokens=toks, length=jnp.full((B,), S), adapter=base,
                 block_tables=tbl)
    full = unified_forward(cfg, params, UnifiedBatch(pf=pf), cache=cache)

    cache = init_paged_cache(cfg, 9, 8, B)
    pts = _split_points(S, n_chunks, rng)
    out = None
    for lo, hi in zip(pts, pts[1:]):
        pf = PFBatch(tokens=toks[:, lo:hi],
                     length=jnp.full((B,), hi - lo), adapter=base,
                     block_tables=tbl,
                     cached_len=jnp.full((B,), lo, jnp.int32))
        out = unified_forward(cfg, params, UnifiedBatch(pf=pf), cache=cache)
        cache = out.cache
    a, b = np.asarray(full.pf_logits), np.asarray(out.pf_logits)
    assert (a.argmax(-1) == b.argmax(-1)).all()
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    for la, lb in zip(full.cache["layers"], cache["layers"]):
        for key in la:
            np.testing.assert_allclose(np.asarray(la[key]),
                                       np.asarray(lb[key]),
                                       rtol=2e-5, atol=2e-5)


def test_model_prefill_kernel_flag(monkeypatch):
    """REPRO_PAGED_ATTN_KERNEL wires the paged attention kernel into the model's
    suffix-prefill bucket: logits must match the jnp gather-view path."""
    cfg = get_reduced("llama3-8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S, cached = 2, 14, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    base = jnp.full((B,), -1)
    tbl = jnp.asarray(np.array([[3, 1, 7, 5], [2, 6, 4, 8]], np.int32))

    def drive():
        cache = init_paged_cache(cfg, 9, 8, B)
        pf = PFBatch(tokens=toks[:, :cached],
                     length=jnp.full((B,), cached), adapter=base,
                     block_tables=tbl,
                     cached_len=jnp.zeros((B,), jnp.int32))
        cache = unified_forward(cfg, params, UnifiedBatch(pf=pf),
                                cache=cache).cache
        pf = PFBatch(tokens=toks[:, cached:],
                     length=jnp.full((B,), S - cached), adapter=base,
                     block_tables=tbl,
                     cached_len=jnp.full((B,), cached, jnp.int32))
        return np.asarray(unified_forward(cfg, params, UnifiedBatch(pf=pf),
                                          cache=cache).pf_logits)

    monkeypatch.delenv("REPRO_PAGED_ATTN_KERNEL", raising=False)
    ref = drive()
    monkeypatch.setenv("REPRO_PAGED_ATTN_KERNEL", "interpret")
    got = drive()
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------------ engine
def _engine(cfg, seed=0, trainers=0, **kw):
    params = init_params(cfg, jax.random.PRNGKey(seed))
    store = AdapterStore(cfg, LCFG, jax.random.PRNGKey(seed + 1))
    store.load_random("serve", jax.random.PRNGKey(seed + 2))
    kw = {"capacity": 4, "pf_capacity": 2, "s_max": 96, "block_size": 16,
          "virtual_time": True, **kw}
    eng = UnifiedEngine(MixedLoraModel(cfg, params, store),
                        EngineConfig(**kw))
    for i in range(trainers):
        name = f"tr{i}"
        store.load_random(name, jax.random.PRNGKey(seed + 10 + i))
        rows, ev = datasets.split_eval(
            datasets.alpaca_like(12, vocab=cfg.vocab, seed=i))
        eng.add_trainer(MixedLoraTrainer(name, store.slot_of(name), rows, ev,
                                         TrainerConfig(rows_per_micro=2,
                                                       accum_steps=2,
                                                       epochs=1)))
    return eng


def _shared_reqs(cfg, n=5, max_new=6, tail=(4, 12), seed=0):
    sys_prompt = np.arange(32, dtype=np.int32) % cfg.vocab
    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=np.concatenate([sys_prompt, rng.integers(
                        0, cfg.vocab, rng.integers(*tail)).astype(np.int32)]),
                    adapter="serve", max_new_tokens=max_new,
                    arrival=0.25 * i) for i in range(n)]


def _run(eng, reqs, max_ticks=8000):
    for r in reqs:
        eng.submit(r)
    eng.run(max_ticks=max_ticks)
    return {r.rid: list(r.output) for r in eng.finished}


@pytest.mark.parametrize("arch", ["llama3-8b", "deepseek-v2-236b"])
def test_engine_suffix_prefill_matches_no_dedup(arch):
    """Suffix-only prefill over hash-adopted blocks emits tokens
    byte-identical to the no-dedup engine, and actually skips work — for
    standard attention AND MLA."""
    cfg = get_reduced(arch)
    out_plain = _run(_engine(cfg, hash_dedup=False), _shared_reqs(cfg))
    eng = _engine(cfg)
    out_shared = _run(eng, _shared_reqs(cfg))
    assert len(out_shared) == 5
    assert out_shared == out_plain
    m = eng.metrics
    assert m.reused_prefix_tokens >= 32 * 4   # 2 full blocks x later reqs
    assert m.hash_hits >= 2 * 4               # adopted from the 2nd sighting
    assert m.starved_ticks == 0


def test_engine_chunked_prefill_matches_unchunked_mixed_ft():
    """Chunked prefill (per-tick token budget) co-batched with fine-tune
    rows: byte-identical tokens, every step under the budget, trainers
    still converge their schedule."""
    cfg = get_reduced("llama3-8b")
    ref = _run(_engine(cfg, trainers=1), _shared_reqs(cfg, tail=(20, 40)))
    eng = _engine(cfg, trainers=1, prefill_chunk=16)
    out = _run(eng, _shared_reqs(cfg, tail=(20, 40)))
    assert out == ref
    m = eng.metrics
    assert m.max_pf_tokens_step <= 16
    assert m.starved_ticks == 0
    assert not eng.prefilling                 # no request left mid-prompt
    assert all(not t.pending() for t in eng.trainers.values())


def test_engine_spec_over_reused_prefix_with_chunking_matches_greedy():
    """Speculative decoding on top of suffix-only + chunked prefill stays
    exactly greedy."""
    cfg = get_reduced("llama3-8b")
    ref = _run(_engine(cfg), _shared_reqs(cfg, max_new=10))
    eng = _engine(cfg, prefill_chunk=16,
                  spec=SpecConfig(k_max=3, drafter="ngram"))
    out = _run(eng, _shared_reqs(cfg, max_new=10))
    assert out == ref


def test_engine_hash_dedup_reuses_repeated_heads():
    """Content-hash dedup: repeated prompt heads get published and adopted
    without any caller-side id — from the SECOND sighting (the two-sighting
    auto_prefix heuristic it subsumes only reused from the third) — and
    outputs stay identical to the escape-hatch engine."""
    cfg = get_reduced("llama3-8b")
    reqs = lambda: _shared_reqs(cfg, n=6)
    ref = _run(_engine(cfg, hash_dedup=False), reqs())
    eng = _engine(cfg)
    out = _run(eng, reqs())
    assert out == ref
    assert eng.metrics.reused_prefix_tokens >= 32 * 5  # 2nd request onward
    assert eng.metrics.hash_hits >= 2 * 5
    assert eng.metrics.hash_blocks_resident >= 2


def test_engine_chunked_prefill_keeps_decode_rows_flowing():
    """While a long prompt prefills in chunks, already-decoding requests
    must receive decode rows in EVERY step (no decode-starved ticks) and
    keep emitting tokens between chunk steps."""
    cfg = get_reduced("llama3-8b")
    eng = _engine(cfg, prefill_chunk=16, s_max=128)
    short = Request(rid=0, prompt=np.arange(6, dtype=np.int32),
                    adapter="serve", max_new_tokens=24, arrival=0.0)
    long_r = Request(rid=1, prompt=np.arange(64, dtype=np.int32) % cfg.vocab,
                     adapter="serve", max_new_tokens=4, arrival=0.0)
    eng.submit(short)
    eng.submit(long_r)
    prev_out = 0
    saw_chunk_with_decode = 0
    for _ in range(200):
        busy = eng.tick()
        if eng.prefilling and short.dec_slot in eng.active:
            # a chunk step ran while rid0 decoded: it must have progressed
            if len(short.output) > prev_out:
                saw_chunk_with_decode += 1
        prev_out = len(short.output)
        if not busy:
            break
    assert saw_chunk_with_decode >= 2         # several co-batched chunk steps
    assert eng.metrics.starved_ticks == 0
    assert len(eng.finished) == 2
    assert eng.metrics.max_pf_tokens_step <= 16
