"""The float32 reference against the program at a reduced width on the
CPU: the same logits for every row's own adapter, and far from them with a
wrong adapter; and ``run.py`` refuses to run without a TPU."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, reference, weights
from perfbench.arch import llama

SPEC = dict(json.loads((harness.HERE / "configs" / "phi3medium-pp4.json")
                       .read_text()),
            hidden_size=128, intermediate_size=256, num_attention_heads=4,
            num_key_value_heads=2, num_hidden_layers=2, vocab_size=256,
            torch_dtype="float32")
TARGETS = SPEC["lora"]["targets"]


class _Cell:
    config = SPEC
    arch = llama


@pytest.fixture(scope="module")
def model():
    cfg = llama.model_config(SPEC)
    lcfg = harness.lora_config(_Cell, 3)
    mp, ma = harness.makers(_Cell, cfg, lcfg, 3)
    params = mp(weights.seed_key(2 ** 33 + 1, 0))
    adapters = ma(weights.seed_key(2 ** 33 + 1, 1))
    return cfg, params, adapters


def program_logits(cfg, params, adapters, tokens, ids):
    """Last-position logits of a plain prefill through the program's
    ``unified_forward``, with the adapters in its bank layout."""
    from repro.models.model import init_cache, unified_forward
    from repro.models.stream import PFBatch, UnifiedBatch
    bank = jax.tree_util.tree_map(lambda x: jnp.moveaxis(x, 0, -3), adapters)
    B, S = tokens.shape
    batch = UnifiedBatch(pf=PFBatch(tokens=jnp.asarray(tokens),
                                    length=jnp.full((B,), S, jnp.int32),
                                    adapter=jnp.asarray(ids, jnp.int32)))
    out = unified_forward(cfg, params, batch, init_cache(cfg, B, S),
                          loras=bank, lora_scale=jnp.ones((3,), jnp.float32))
    return np.asarray(out.pf_logits)


def ref_logits(ref, params, adapters, tokens, ids):
    seqs = [dict(prompt=t, output=np.zeros(1, np.int32), adapter=a)
            for t, a in zip(tokens, ids)]
    return np.stack([x[0] for x in ref.served_logits(params, adapters,
                                                     seqs, tokens.shape[1], 1)])


def test_reference_matches_program(model):
    cfg, params, adapters = model
    tokens = np.random.default_rng(0).integers(0, 256, (3, 24)).astype(
        np.int32)
    ids = np.array([0, 1, 2])
    got = program_logits(cfg, params, adapters, tokens, ids)
    ref = llama.Reference(SPEC)
    want = ref_logits(ref, params, adapters, tokens, ids)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale
    wrong = ref_logits(ref, params, adapters, tokens, (ids + 1) % 3)
    assert np.abs(got - wrong).max() > 100 * np.abs(got - want).max()
    ctl = ref_logits(llama.Reference(SPEC, fp8=True), params, adapters,
                     tokens, ids)
    assert np.abs(ctl - want).max() > 100 * np.abs(got - want).max()


def test_reference_losses(model):
    """Per-row fine-tune losses, and the gradient of their weighted sum
    with respect to the trained adapter, against the program's grad step."""
    cfg, params, adapters = model
    from repro.core.unified import make_grad_step
    from repro.models.stream import FTBatch, UnifiedBatch
    rng = np.random.default_rng(1)
    toks = rng.integers(0, 256, (2, 16)).astype(np.int32)
    mask = np.ones((2, 16), bool)
    mask[1, 10:] = False
    bank = jax.tree_util.tree_map(lambda x: jnp.moveaxis(x, 0, -3), adapters)
    res = make_grad_step(cfg)(params, bank, jnp.ones((3,), jnp.float32),
                              UnifiedBatch(ft=FTBatch(
        tokens=jnp.asarray(toks), mask=jnp.asarray(mask),
        labels=jnp.asarray(np.where(mask, toks, -100)),
        adapter=jnp.asarray([2, 2], jnp.int32),
        weight=jnp.full((2,), 0.25, jnp.float32))), None)
    got = np.asarray(res.out.ft_loss_sum) / np.asarray(res.out.ft_tok_count)
    one = {k: {"a": v["a"][2][None], "b": v["b"][2][None]}
           for k, v in adapters["blocks"][0].items()
           if k in TARGETS}
    want, g = llama.Reference(SPEC).grads(
        params, one, [(toks[0], 0.25), (toks[1, :10], 0.25)], 16)
    assert got == pytest.approx(want, rel=1e-5)
    pg = reference.leaves(jax.tree_util.tree_map(lambda x: x[..., 2, :, :],
                                                 res.grads), TARGETS)
    rg = reference.leaves(jax.tree_util.tree_map(lambda x: x[0], g), TARGETS)
    assert set(pg) == set(rg) and len(rg) == 14
    for k in rg:
        assert np.abs(pg[k] - rg[k]).max() <= 1e-4 * np.abs(rg[k]).max(), k


def test_leaf_gap():
    want = {"wq.a": np.array([3.0, 4.0]), "wq.b": np.array([0.0, 1.0]),
            "wk.a": np.array([0.0, 2.0])}
    got = {"wq.a": np.array([4.0, 3.0]), "wq.b": np.array([0.0, 1.5]),
           "wk.a": np.array([0.0, 2.0])}
    keep = sorted(want)
    # norms 5, 1, 2: the median (2) is the floor under the small leaf
    assert reference.leaf_gap(got, want, keep) == pytest.approx(0.25)
    assert reference.leaf_gap(got, want, keep, diff=True) == pytest.approx(
        2 ** 0.5 / 5)
    assert reference.moving_leaves(
        {"a": np.ones(3), "b": np.ones(3), "c": np.full(3, 1e-4)}) == ["a", "b"]


def test_token_gaps():
    lg = [np.array([[0.0, 2.0, 1.0], [3.0, 0.0, 2.5]])]
    assert list(reference.token_gaps(lg, [[1, 2]])) == pytest.approx(
        [0.0, 0.5])


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(harness.HERE / "run.py"), "--workload",
         "deepseek7b.chat-saturate", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU" in p.stderr
