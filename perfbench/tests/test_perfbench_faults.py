"""A whole run at the tiny size, with the chip check skipped: sound, it
comes out correct; with the timed path broken underneath, it comes out not
correct.  And the float8 control reads above the cell's limits."""
import gc
import time

import jax
import jax.numpy as jnp
import pytest

from perfbench import harness

CELLS = ["deepseek7b.chat-coserve", "deepseek7b.chat-saturate"]


def run(cell, seed=2 ** 32 + 3):
    return harness.run_cell(cell, seed, 2.0, False, time.perf_counter(),
                            log=lambda *_: None)


def _alter(step, fault):
    def wrapped(base, bank, scale, batch, cache):
        if fault == "ft_rows_halved" and batch.ft is not None:
            # half of the fine-tune rows left out, the mean over the rest
            w = batch.ft.weight
            n = int((w > 0).sum())
            keep = (n + 1) // 2
            idx = jnp.arange(w.shape[0])
            batch = batch._replace(ft=batch.ft._replace(
                weight=jnp.where(idx < keep, w * n / max(keep, 1), 0.0)))
        res = step(base, bank, scale, batch, cache)
        out = getattr(res, "out", res)
        if fault == "token_altered" and out.dec_logits is not None:
            out = out._replace(dec_logits=out.dec_logits.at[:, 7].add(1e4))
        elif fault == "state_unchanged" and cache is not None:
            out = out._replace(cache=cache)
        elif fault == "half_batch" and out.dec_logits is not None:
            h = out.dec_logits.shape[0] // 2
            out = out._replace(dec_logits=jnp.concatenate(
                [out.dec_logits[:h], out.dec_logits[:h]]))
        elif fault == "grads_zeroed" and hasattr(res, "grads"):
            res = res._replace(grads=jax.tree_util.tree_map(jnp.zeros_like,
                                                            res.grads))
        return res._replace(out=out) if hasattr(res, "out") else out
    return wrapped


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct(tiny_cell, cell_name):
    res = run(tiny_cell(cell_name))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 10 and res["failed"] == 0
    assert set(res["metrics"]) >= {"setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch", "grads_zeroed",
                                   "ft_rows_halved", "apply_skipped"])
def test_broken_step_is_not_correct(tiny_cell, monkeypatch, fault):
    from repro.serving import engine
    for name in ("make_forward_step", "make_grad_step"):
        make = getattr(engine, name)
        monkeypatch.setattr(engine, name,
                            lambda cfg, _m=make, **kw: _alter(_m(cfg, **kw),
                                                              fault))
    if fault == "apply_skipped":
        # the optimizer step returns the adapters and its state unchanged
        monkeypatch.setattr(engine, "make_apply_step",
                            lambda opt: lambda g, state, bank, mask:
                            (bank, state))
    res = run(tiny_cell(CELLS[0]))
    assert not res["correct"], res["checks"]
    gc.collect()


def test_control_is_not_correct(tiny_cell):
    cell = tiny_cell(CELLS[0])
    compiles = harness.compile_seconds()
    seed = 12
    built = harness.build(cell, seed)
    win = harness.serve(cell, built, seed, 2.0, cell.setup["rate_rps"],
                        False, time.perf_counter(), compiles,
                        log=lambda *_: None)
    got = harness.compare(cell, built.make_params, built.make_adapters, seed,
                          win.picked, win.training,
                          built.names.index(harness.TRAINED), study=True)
    lim = cell.setup["limits"]
    assert all(got[k] <= v for k, v in lim.items()), got
    for tag in ("control", "half_rows", "state_unchanged"):
        assert any(got[f"{k}.{tag}"] > v for k, v in lim.items()
                   if f"{k}.{tag}" in got), (tag, got)
