"""The unified step addresses its cache in place: each sublayer scatters into
the leaves stacked over layers at its layer index and reads them there, and
cache writes carry no derivative.  Checked against the form this replaced,
rebuilt here from the same sublayers: each layer's cache sliced out, run at
index 0 and written back, with cache writes differentiated.  Pools, logits
and bank gradients must match bit for bit."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.core.lora import LoRAConfig
from repro.core.unified import scalar_loss
from repro.core.virtualization import AdapterStore
from repro.models import model
from repro.models.model import init_cache, init_paged_cache, unified_forward
from repro.models.schema import init_params
from repro.models.stream import DECBatch, FTBatch, PFBatch, UnifiedBatch

LCFG = LoRAConfig(n_slots=2, r=4)
S, SF, SP = 10, 12, 6        # seeded prompt, fine-tune row, prefill row


def _sliced(apply, at):
    """``apply`` with its stacked cache (argument ``at``, layer index after
    it) replaced by the layer sliced out, run at index 0, written back."""
    def run(*args):
        args = list(args)
        stacked, idx = args[at], args[at + 1]
        args[at] = jax.tree_util.tree_map(
            lambda c: jax.lax.dynamic_slice_in_dim(c, idx, 1), stacked)
        args[at + 1] = jnp.int32(0)
        x, layer = apply(*args)
        return x, jax.tree_util.tree_map(
            lambda c, n: jax.lax.dynamic_update_slice_in_dim(c, n, idx, 0),
            stacked, layer)
    return run


@pytest.fixture
def sliced_form(monkeypatch):
    def use():
        monkeypatch.setattr(model, "_attn_apply",
                            _sliced(model._attn_apply, 6))
        monkeypatch.setattr(model, "_mamba_apply",
                            _sliced(model._mamba_apply, 5))
        monkeypatch.setattr(model, "_cache_value",
                            lambda x, dtype: x.astype(dtype))
    return use


def _batches(cfg, paged):
    """A seeding prefill of one row, then one unified step carrying a
    fine-tune row, a prefill row and a decode row of the seeded request,
    all on adapter 0 (so the K/V they write depend on the bank)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    tok = lambda k, *s: jax.random.randint(k, s, 0, cfg.vocab)
    one = jnp.zeros((1,), jnp.int32)
    dec_tbl = jnp.asarray([[3, 1, 0, 0]], jnp.int32) if paged else None
    pf_tbl = jnp.asarray([[5, 2, 0, 0]], jnp.int32) if paged else None
    seed = PFBatch(tokens=tok(ks[0], 1, S), length=jnp.full((1,), S),
                   adapter=one, block_tables=dec_tbl)
    ft = FTBatch(tokens=tok(ks[1], 1, SF), mask=jnp.ones((1, SF), bool),
                 labels=tok(ks[2], 1, SF), adapter=one,
                 weight=jnp.ones((1,)))
    pf = PFBatch(tokens=tok(ks[3], 1, SP), length=jnp.full((1,), SP - 1),
                 adapter=one, block_tables=pf_tbl,
                 cached_len=one if paged else None)
    dec = DECBatch(tokens=tok(ks[4], 1), pos=jnp.full((1,), S), adapter=one,
                   block_tables=dec_tbl)
    return seed, UnifiedBatch(ft=ft, pf=pf, dec=dec)


def _run(cfg, paged):
    params = init_params(cfg, jax.random.PRNGKey(0))
    store = AdapterStore(cfg, LCFG, jax.random.PRNGKey(1))
    store.load_random("x", jax.random.PRNGKey(2))
    seed, step = _batches(cfg, paged)
    cache = (init_paged_cache(cfg, 9, 8, 2) if paged
             else init_cache(cfg, 2, 16))

    def fwd(bank, batch, cache):
        return unified_forward(cfg, params, batch, cache, loras=bank,
                               lora_scale=store.scale)

    def loss(bank, batch, cache):
        out = fwd(bank, batch, cache)
        return scalar_loss(out, batch), out

    seeded = jax.jit(fwd)(store.bank, UnifiedBatch(pf=seed), cache).cache
    out = jax.jit(fwd)(store.bank, step, seeded)
    (_, gout), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(
        store.bank, step, seeded)
    return {"seeded": seeded, "cache": out.cache, "grad_cache": gout.cache,
            "logits": (out.pf_logits, out.dec_logits, out.ft_loss_sum),
            "grads": grads}


CASES = {
    "attn-paged": ("llama3-8b", True),
    "attn-rows": ("llama3-8b", False),
    "mla-paged": ("deepseek-v2-236b", True),
    "hybrid-mamba-paged": ("jamba-1.5-large-398b", True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_in_place_cache_matches_sliced_layers(sliced_form, case):
    arch, paged = CASES[case]
    cfg = get_reduced(arch)
    # two periods at least, so a layer index other than 0 is addressed
    cfg = cfg.replace(n_layers=max(cfg.n_periods, 2) * len(cfg.pattern))
    if cfg.moe is not None:
        # the router's balance loss reads every row of the stream, so with
        # it the sliced form's gradient would also flow through the
        # inference rows' freshly written K/V; without it the loss reads
        # fine-tune rows alone, as in a dense model
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  router_aux_weight=0.0))
    got = _run(cfg, paged)
    sliced_form()
    want = _run(cfg, paged)
    flat = jax.tree_util.tree_leaves_with_path(want)
    assert len(flat) == len(jax.tree_util.tree_leaves(got))
    assert any(float(jnp.abs(g).max()) > 0
               for g in jax.tree_util.tree_leaves(got["grads"]))
    for (path, w), g in zip(flat, jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))
