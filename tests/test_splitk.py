"""Flash-decoding split-K paged attention: kernels vs oracles, LSE-merge
algebra (property-based), the autotune table/heuristic, kernel-mode env
validation, model-level dispatch, and engine-level byte-exactness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels.autotune import AttnConfig
from repro.kernels.decode_attn import paged_attention
from repro.kernels.ref import paged_decode_ref, paged_verify_ref
from repro.kernels.splitk import lse_merge, paged_verify_attention_splitk
from _hyputil import given, hyp as _hyp, settings, st

NEG = -1e30


def _paged_setup(B, g, hd, bs, nbt, n_blocks, pos, seed=0, Sq=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    k_pool = jax.random.normal(ks[0], (n_blocks, bs, g, hd))
    v_pool = jax.random.normal(ks[1], (n_blocks, bs, g, hd))
    rng = np.random.default_rng(seed)
    tables = np.zeros((B, nbt), np.int32)
    for b in range(B):
        # blocks covering positions 0 .. pos + max(Sq, 1) - 1
        need = -(-(pos[b] + max(Sq, 1)) // bs)
        tables[b, :need] = rng.choice(np.arange(1, n_blocks), size=need,
                                      replace=False)
    return k_pool, v_pool, jnp.asarray(tables), ks[2]


# ------------------------------------------------------------------ kernels
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ns", [1, 2, 4, 7])
def test_splitk_decode_matches_ref(dtype, ns):
    """Split-K decode == gather-then-attend oracle for every fan-out,
    including a non-divisor split count, over scattered tables."""
    B, h, g, hd, bs, nbt = 3, 8, 2, 16, 8, 5
    pos = np.minimum(np.arange(B) * 13 + 3, nbt * bs - 1)
    k_pool, v_pool, tables, kq = _paged_setup(B, g, hd, bs, nbt,
                                              nbt * B + 2, pos)
    q = jax.random.normal(kq, (B, h, hd)).astype(dtype)
    k_pool, v_pool = k_pool.astype(dtype), v_pool.astype(dtype)
    posj = jnp.asarray(pos, jnp.int32)
    y = paged_verify_attention_splitk(q[:, None], k_pool, v_pool, tables,
                                      posj, jnp.ones((B,), jnp.int32),
                                      num_splits=ns, interpret=True)[:, 0]
    yr = paged_decode_ref(q, k_pool, v_pool, tables, posj)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("ns", [1, 2, 4, 7])
def test_splitk_verify_matches_ref(ns):
    """Split-K verify (multi-token chunk, ragged lens incl. a padding row)
    == oracle for every fan-out."""
    B, h, g, hd, bs, nbt, Sq = 3, 8, 2, 16, 8, 5, 4
    rng = np.random.default_rng(ns)
    pos = np.minimum(np.arange(B) * 5 + 2, nbt * bs - Sq - 1)
    lens = np.array([Sq, 2, 0])          # full, partial, padding row
    k_pool, v_pool, tables, kq = _paged_setup(B, g, hd, bs, nbt, nbt * B + 2,
                                              pos, seed=ns, Sq=Sq)
    q = jax.random.normal(kq, (B, Sq, h, hd))
    posj, lensj = jnp.asarray(pos, jnp.int32), jnp.asarray(lens, jnp.int32)
    y = paged_verify_attention_splitk(q, k_pool, v_pool, tables, posj, lensj,
                                      num_splits=ns, interpret=True)
    yr = paged_verify_ref(q, k_pool, v_pool, tables, posj, lensj)
    valid = lens[:, None] > np.arange(Sq)[None, :]     # padding rows/slots
    np.testing.assert_allclose(np.asarray(y)[valid], np.asarray(yr)[valid],
                               rtol=3e-5, atol=3e-5)


def test_splitk_matches_sequential_kernel():
    """Split-K and the sequential walk are the SAME attention — compare the
    two Pallas kernels directly (not just both-vs-oracle)."""
    B, h, g, hd, bs, nbt = 2, 4, 2, 16, 8, 4
    pos = np.array([13, 30])
    k_pool, v_pool, tables, kq = _paged_setup(B, g, hd, bs, nbt, 16, pos)
    q = jax.random.normal(kq, (B, h, hd))
    posj = jnp.asarray(pos, jnp.int32)
    ones = jnp.ones((B,), jnp.int32)
    y_seq = paged_attention(q[:, None], k_pool, v_pool, tables, posj, ones,
                            interpret=True)
    y_spl = paged_verify_attention_splitk(q[:, None], k_pool, v_pool, tables,
                                          posj, ones, num_splits=4,
                                          interpret=True)
    np.testing.assert_allclose(np.asarray(y_spl), np.asarray(y_seq),
                               rtol=2e-5, atol=2e-5)
    lens = jnp.asarray([3, 1], jnp.int32)
    qv = jax.random.normal(kq, (B, 4, h, hd))
    yv_seq = paged_attention(qv, k_pool, v_pool, tables, posj, lens,
                             interpret=True)
    yv_spl = paged_verify_attention_splitk(qv, k_pool, v_pool, tables, posj,
                                           lens, num_splits=3, interpret=True)
    valid = np.asarray(lens)[:, None] > np.arange(4)[None, :]
    np.testing.assert_allclose(np.asarray(yv_spl)[valid],
                               np.asarray(yv_seq)[valid],
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- lse_merge
def _single_pass(scores, values):
    """Plain softmax-weighted sum — the oracle lse_merge must reproduce."""
    m = scores.max()
    w = np.exp(scores - m)
    return (w[:, None] * values).sum(0) / w.sum()


def _partials(scores, values, cuts):
    """Build per-split (o, m, l) exactly as the kernel's online softmax
    emits them: un-normalized, split-local maxima, -inf/0 when empty."""
    o, ms, ls = [], [], []
    for lo, hi in cuts:
        s, v = scores[lo:hi], values[lo:hi]
        if len(s) == 0:
            o.append(np.zeros(values.shape[1])); ms.append(NEG); ls.append(0.)
            continue
        m = s.max()
        w = np.exp(s - m)
        o.append((w[:, None] * v).sum(0)); ms.append(m); ls.append(w.sum())
    return np.stack(o), np.array(ms), np.array(ls)


def _merge_np(o, m, l):
    """lse_merge on a single (ns,)-indexed problem via the jnp kernel."""
    out = lse_merge(jnp.asarray(o, jnp.float32)[None, :, None, None],
                    jnp.asarray(m, jnp.float32)[None, :, None, None],
                    jnp.asarray(l, jnp.float32)[None, :, None, None])
    return np.asarray(out)[0, 0, 0]


def test_lse_merge_all_empty_degenerates_to_zero():
    """ALL-masked splits (m = -inf, l = 0 everywhere) must merge to exactly
    zero — matching the sequential kernels' all-masked finalize — without
    NaNs from the 0/0."""
    o = np.zeros((3, 8))
    out = _merge_np(o, np.full(3, NEG), np.zeros(3))
    assert np.all(out == 0.0) and not np.any(np.isnan(out))


def test_lse_merge_empty_split_is_inert():
    """An empty split among non-empty ones must not perturb the result."""
    rng = np.random.default_rng(0)
    s, v = rng.standard_normal(12), rng.standard_normal((12, 8))
    o, m, l = _partials(s, v, [(0, 7), (7, 7), (7, 12)])  # middle split empty
    np.testing.assert_allclose(_merge_np(o, m, l), _single_pass(s, v),
                               rtol=1e-6, atol=1e-6)


@_hyp(lambda: [settings(max_examples=40, deadline=None),
               given(n=st.integers(1, 48), ns=st.sampled_from([1, 2, 4, 7]),
                     seed=st.integers(0, 2**16), shift=st.floats(-50, 50))])
def test_lse_merge_equals_single_pass(n, ns, seed, shift):
    """PROPERTY: merge-of-partials == single-pass softmax for any ragged
    split of any score sequence, including large uniform shifts (the case
    naive exp() overflows on and the m-subtraction must absorb)."""
    rng = np.random.default_rng(seed)
    s = rng.standard_normal(n) * 3 + shift
    v = rng.standard_normal((n, 4))
    edges = np.sort(rng.integers(0, n + 1, ns - 1)) if ns > 1 else np.array([], int)
    bounds = [0, *edges.tolist(), n]
    cuts = list(zip(bounds[:-1], bounds[1:]))          # may include empties
    o, m, l = _partials(s, v, cuts)
    np.testing.assert_allclose(_merge_np(o, m, l), _single_pass(s, v),
                               rtol=1e-5, atol=1e-5)


@_hyp(lambda: [settings(max_examples=12, deadline=None),
               given(B=st.integers(1, 3), g=st.sampled_from([1, 2, 4]),
                     ns=st.sampled_from([1, 2, 4, 7]),
                     seed=st.integers(0, 2**16), bf16=st.booleans())])
def test_splitk_kernel_property(B, g, ns, seed, bf16):
    """PROPERTY: the split-K kernel == oracle across batch sizes, GQA group
    sizes, fan-outs, pool dtypes, and random ragged positions."""
    h, hd, bs, nbt = 4, 8, 8, 5
    rng = np.random.default_rng(seed)
    pos = rng.integers(0, nbt * bs, B)
    k_pool, v_pool, tables, kq = _paged_setup(B, g, hd, bs, nbt,
                                              nbt * B + 2, pos, seed=seed)
    dtype = jnp.bfloat16 if bf16 else jnp.float32
    q = jax.random.normal(kq, (B, h, hd)).astype(dtype)
    k_pool, v_pool = k_pool.astype(dtype), v_pool.astype(dtype)
    posj = jnp.asarray(pos, jnp.int32)
    y = paged_verify_attention_splitk(q[:, None], k_pool, v_pool, tables,
                                      posj, jnp.ones((B,), jnp.int32),
                                      num_splits=ns, interpret=True)[:, 0]
    yr = paged_decode_ref(q, k_pool, v_pool, tables, posj)
    tol = 2e-5 if not bf16 else 5e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=tol, atol=tol)


# ----------------------------------------------------------------- autotune
def test_heuristic_splits_small_batch_only():
    """Long context + small batch -> split; bh >= lanes -> sequential; a
    short table is never sharded below MIN_BLOCKS_PER_SPLIT blocks."""
    assert autotune.heuristic(64, 16, 32, 4).num_splits > 1
    assert autotune.heuristic(64, 16, 32, 64).num_splits == 1
    assert autotune.heuristic(64, 16, 4, 1).num_splits == 1
    for nbt in (4, 32):
        for ns in autotune.candidate_splits(nbt):
            assert ns == 1 or -(-nbt // ns) >= autotune.MIN_BLOCKS_PER_SPLIT


def test_modeled_time_monotone_in_waves():
    """The occupancy model must reward splitting exactly while extra splits
    still fill idle lanes, then punish past saturation."""
    t1 = autotune.modeled_grid_time(4, 32, 1)
    t4 = autotune.modeled_grid_time(4, 32, 4)
    t16 = autotune.modeled_grid_time(4, 32, 16)
    assert t4 < t1                       # 4 cells can't fill 16 lanes
    assert t16 > t4                      # 64 cells oversubscribe them


def test_table_overrides_heuristic_and_bumps_version():
    key = (64, 16, 32, 4)
    try:
        v0 = autotune.table_version()
        assert autotune.choose(*key).num_splits > 1     # heuristic
        autotune.put_config(key, AttnConfig(256, 1))
        assert autotune.table_version() == v0 + 1       # cache-key bump
        assert autotune.choose(*key) == AttnConfig(256, 1)
    finally:
        autotune.clear_table()
    assert autotune.choose(*key).num_splits > 1         # fallback restored


def test_lanes_env_override_shifts_heuristic(monkeypatch, tmp_path):
    """REPRO_ATTN_LANES calibrates the occupancy model per device: a bigger
    lane count makes the same shape under-occupied, flipping the heuristic
    from sequential to split; the persisted table records the lanes the
    sweep modeled with; garbage values fail loudly instead of silently
    falling back to the default."""
    monkeypatch.delenv(autotune.ENV_LANES, raising=False)
    assert autotune.effective_lanes() == autotune.LANES
    # bh = 16 fills 16 default lanes (no split)...
    assert autotune.heuristic(64, 16, 32, 16).num_splits == 1
    monkeypatch.setenv(autotune.ENV_LANES, "64")
    assert autotune.effective_lanes() == 64
    # ...but cannot fill 64 — the SAME shape now wants a split, and the
    # explicit-lanes argument matches what the env default resolves to
    assert autotune.heuristic(64, 16, 32, 16).num_splits > 1
    assert autotune.heuristic(64, 16, 32, 16) \
        == autotune.heuristic(64, 16, 32, 16, lanes=64)
    p = str(tmp_path / "tune.json")
    try:
        autotune.put_config((64, 16, 32, 16), AttnConfig(512, 4))
        autotune.save_table(p)
    finally:
        autotune.clear_table()
    import json
    assert json.load(open(p))["lanes"] == 64
    # validation: non-integers and non-positive counts raise, with the
    # variable named so the error is actionable; empty means default
    monkeypatch.setenv(autotune.ENV_LANES, "sixteen")
    with pytest.raises(ValueError, match="REPRO_ATTN_LANES"):
        autotune.effective_lanes()
    with pytest.raises(ValueError):
        autotune.heuristic(64, 16, 32, 16)     # reaches every choice path
    monkeypatch.setenv(autotune.ENV_LANES, "0")
    with pytest.raises(ValueError, match=">= 1"):
        autotune.effective_lanes()
    monkeypatch.setenv(autotune.ENV_LANES, "  ")
    assert autotune.effective_lanes() == autotune.LANES


def test_table_save_load_roundtrip(tmp_path):
    p = str(tmp_path / "tune.json")
    try:
        autotune.put_config((64, 16, 32, 4), AttnConfig(512, 4))
        autotune.put_config((32, 8, 8, 16), AttnConfig(256, 1))
        assert autotune.save_table(p) == 2
        autotune.clear_table()
        assert autotune.get_config((64, 16, 32, 4)) is None
        assert autotune.load_table(p) == 2
        assert autotune.get_config((64, 16, 32, 4)) == AttnConfig(512, 4)
        assert autotune.get_config((32, 8, 8, 16)) == AttnConfig(256, 1)
    finally:
        autotune.clear_table()


def test_load_table_rejects_malformed(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text('{"entries": {"64,16,32": [512, 4]}}')
    with pytest.raises(ValueError, match="malformed"):
        autotune.load_table(str(p))


def test_sweep_populates_table():
    try:
        chosen = autotune.sweep([(64, 16, 32, 4), (64, 16, 32, 64)])
        assert chosen[(64, 16, 32, 4)].num_splits > 1
        assert chosen[(64, 16, 32, 64)].num_splits == 1
        assert autotune.get_config((64, 16, 32, 4)) is not None
        # a measure= hook (real-TPU wall clock) overrides the model
        flat = autotune.sweep([(64, 16, 32, 4)],
                              measure=lambda key, cfg: float(cfg.num_splits))
        assert flat[(64, 16, 32, 4)].num_splits == 1    # smaller is "faster"
    finally:
        autotune.clear_table()


# ------------------------------------------------------------- env plumbing
def test_kernel_mode_env_validation(monkeypatch):
    """Unrecognized REPRO_PAGED_ATTN_KERNEL values must fail LOUDLY — a typo
    silently selecting the compiled-TPU path was the prior behavior."""
    from repro.models.model import _paged_kernel_mode
    for v, want in [("", ""), ("0", ""), ("off", ""), ("false", ""),
                    ("1", "tpu"), ("tpu", "tpu"), ("interpret", "interpret"),
                    ("splitk", "splitk"),
                    ("Splitk-Interpret", "splitk-interpret")]:
        monkeypatch.setenv("REPRO_PAGED_ATTN_KERNEL", v)
        assert _paged_kernel_mode() == want
    for bad in ("interpert", "split-k", "yes", "pallas"):
        monkeypatch.setenv("REPRO_PAGED_ATTN_KERNEL", bad)
        with pytest.raises(ValueError, match="REPRO_PAGED_ATTN_KERNEL"):
            _paged_kernel_mode()


# ------------------------------------------------------------ model dispatch
def _drive_decode(cfg, params, toks, tbl, B, S):
    from repro.models.model import init_paged_cache, unified_forward
    from repro.models.stream import DECBatch, PFBatch, UnifiedBatch
    base = jnp.full((B,), -1)
    cache = init_paged_cache(cfg, 9, 8, B)
    pf = PFBatch(tokens=toks[:, :S], length=jnp.full((B,), S),
                 adapter=base, block_tables=tbl)
    cache = unified_forward(cfg, params, UnifiedBatch(pf=pf),
                            cache=cache).cache
    dec = DECBatch(tokens=toks[:, S], pos=jnp.full((B,), S),
                   adapter=base, block_tables=tbl)
    return np.asarray(unified_forward(cfg, params, UnifiedBatch(dec=dec),
                                      cache=cache).dec_logits)


def test_model_decode_bucket_splitk_flag(monkeypatch):
    """splitk-interpret must reproduce the jnp gather-view logits through
    the model — with the fan-out FORCED to a non-trivial value via the
    tuning table (the reduced config's heuristic might pick ns = 1)."""
    from repro.configs import get_reduced
    cfg = get_reduced("llama3-8b")
    from repro.models.schema import init_params
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                              cfg.vocab)
    tbl = jnp.asarray(np.array([[3, 1, 7, 5], [2, 6, 4, 8]], np.int32))
    monkeypatch.delenv("REPRO_PAGED_ATTN_KERNEL", raising=False)
    ref = _drive_decode(cfg, params, toks, tbl, B, S)
    try:
        # key: (cfg.hd, block_size=pool bs (init_paged_cache -> 8), nbt=4,
        # bh = B * n_kv_heads); ns=3 exercises the non-divisor path in-model
        autotune.put_config((cfg.hd, 8, 4, B * cfg.n_kv_heads),
                            AttnConfig(512, 3))
        monkeypatch.setenv("REPRO_PAGED_ATTN_KERNEL", "splitk-interpret")
        got = _drive_decode(cfg, params, toks, tbl, B, S)
    finally:
        autotune.clear_table()
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-4)


def test_model_verify_bucket_splitk_flag(monkeypatch):
    """The verify bucket under splitk-interpret == the jnp path, valid rows
    only (ragged lens; padding slots are never read by the engine)."""
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
    try:
        autotune.put_config((cfg.hd, 8, 4, B * cfg.n_kv_heads),
                            AttnConfig(512, 2))
        monkeypatch.setenv("REPRO_PAGED_ATTN_KERNEL", "splitk-interpret")
        got = drive()
    finally:
        autotune.clear_table()
    valid = lens[:, None] > np.arange(k + 1)[None, :]
    np.testing.assert_allclose(got[valid], ref[valid], rtol=2e-4, atol=2e-4)


def test_model_splitk_keys_autotune_on_grid_cells(monkeypatch):
    """The split count is chosen for the grid that runs: one cell per
    (request, KV head), so the shape key's ``bh`` is B * n_kv_heads."""
    from repro.configs import get_reduced
    from repro.models.schema import init_params
    cfg = get_reduced("llama3-8b")
    assert cfg.n_kv_heads < cfg.n_heads          # GQA: the two keys differ
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                              cfg.vocab)
    tbl = jnp.asarray(np.array([[3, 1, 7, 5], [2, 6, 4, 8]], np.int32))
    keys = []

    def spy(*key):
        keys.append(key)
        return AttnConfig(512, 2)

    monkeypatch.setattr(autotune, "choose", spy)
    monkeypatch.setenv("REPRO_PAGED_ATTN_KERNEL", "splitk-interpret")
    _drive_decode(cfg, params, toks, tbl, B, S)
    assert keys and set(keys) == {(cfg.hd, 8, 4, B * cfg.n_kv_heads)}


# ------------------------------------------------------------------- engine
def test_engine_byte_identical_across_kernel_modes(monkeypatch):
    """End-to-end greedy outputs must be BYTE-identical with the kernel
    family off, sequential, and split-K — same engine, same workload, spec
    decoding on (exercises decode AND verify buckets)."""
    from repro.configs import get_reduced
    from repro.core.lora import LoRAConfig
    from repro.core.virtualization import AdapterStore, MixedLoraModel
    from repro.models.schema import init_params
    from repro.serving.engine import EngineConfig, UnifiedEngine
    from repro.serving.request import Request
    from repro.spec import SpecConfig

    cfg = get_reduced("llama3-8b")
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, rng.integers(6, 24)).astype(
        np.int32) for _ in range(3)]

    def run():
        params = init_params(cfg, jax.random.PRNGKey(0))
        store = AdapterStore(cfg, LoRAConfig(n_slots=4, r=4),
                             jax.random.PRNGKey(1))
        store.load_random("serve", jax.random.PRNGKey(2))
        eng = UnifiedEngine(MixedLoraModel(cfg, params, store),
                            EngineConfig(capacity=4, pf_capacity=2, s_max=96,
                                         block_size=16, virtual_time=True,
                                         spec=SpecConfig(k_max=3,
                                                         drafter="ngram")))
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, adapter="serve",
                               max_new_tokens=6, arrival=0.2 * i))
        eng.run(max_ticks=5000)
        assert len(eng.finished) == 3
        return {r.rid: list(r.output) for r in eng.finished}

    outs = {}
    for mode in ("", "interpret", "splitk-interpret"):
        if mode:
            monkeypatch.setenv("REPRO_PAGED_ATTN_KERNEL", mode)
        else:
            monkeypatch.delenv("REPRO_PAGED_ATTN_KERNEL", raising=False)
        outs[mode] = run()
    assert outs[""] == outs["interpret"] == outs["splitk-interpret"]
