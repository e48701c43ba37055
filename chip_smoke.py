#!/usr/bin/env python3
"""One-chip smoke run of the unified multi-LoRA serve + fine-tune path.

Drives the engine a user builds (``UnifiedEngine`` + ``MixedLoraTrainer``)
once, at the published widths of Llama-3-8B, the paper's base model, with
random weights made from a seed:

  A   co-serving on the default path: 12 requests over 3 adapters, served
      on the wall clock, while a trainer fine-tunes a fourth adapter in the
      same steps;
  A'  the first-token logits of one request agree with a plain unbatched
      ``unified_forward`` of the same weights, and those of the same
      request with a wrong adapter or a wrong KV block do not;
  B   the same requests with the compiled Pallas paged-attention kernels
      (``REPRO_PAGED_ATTN_KERNEL=tpu``) and no trainer: greedy tokens equal
      A's, or differ only where A's and B's logits agree within tolerance.

Every phase raises on a failed check.  Without a TPU the script exits
non-zero before any phase and prints no result line; otherwise the last
line of stdout is ``{"ok": true, "device": {...}}``.

    python3 chip_smoke.py
"""
from __future__ import annotations

import copy
import functools
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# bf16 through 16 layers: max |logit difference| over max |reference logit|.
# Phase A' also reads two faults the check must catch (a wrong adapter, a
# KV block holding another request's tokens) and fails unless both land
# above this limit.
LOGIT_RTOL = 0.05
ARCH, LAYERS = "llama3-8b", 16
N_REQUESTS, MAX_NEW, SEED = 12, 16, 0
SERVED = ("chat", "code", "math")
TRAINED = "student"


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def smoke_config():
    """Llama-3-8B at its published widths, bf16, 16 of its 32 layers: one
    stage of a two-chip pipeline deployment (the whole bf16 model, 16.06
    GB, does not fit one v5e chip's 16 GB)."""
    from repro.configs import get_config
    return get_config(ARCH).replace(n_layers=LAYERS, dtype="bfloat16")


def make_requests(cfg):
    """``N_REQUESTS`` requests round-robin over ``SERVED``.  Each adapter's
    requests share a 128-token system prompt (four KV blocks the dedup
    index lets later requests adopt) followed by a 32-384-token user
    turn."""
    from repro.serving.request import Request
    rng = np.random.default_rng(SEED)
    system = {a: rng.integers(0, cfg.vocab, 128) for a in SERVED}
    reqs = []
    for i in range(N_REQUESTS):
        adapter = SERVED[i % len(SERVED)]
        user = rng.integers(0, cfg.vocab, int(rng.integers(32, 385)))
        prompt = np.concatenate([system[adapter], user]).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, adapter=adapter,
                            max_new_tokens=MAX_NEW))
    return reqs


class LogitRecorder:
    """Wraps an engine's step functions and keeps, per (request id, output
    index), the logits row the engine sampled that token from."""

    def __init__(self, eng):
        self.eng = eng
        self.rows = {}
        self.dec_step = None         # (jitted step, argument shapes)
        self.fwd, self.grad = eng.forward_step, eng.grad_step
        eng.forward_step = lambda *a: self._see(a, self.fwd(*a), self.fwd)
        eng.grad_step = lambda *a: self._see(a, self.grad(*a), self.grad)

    @property
    def shapes_compiled(self) -> int:
        return self.fwd._cache_size() + self.grad._cache_size()

    def _see(self, args, res, fn):
        from repro.serving.request import State
        out = getattr(res, "out", res)
        batch = args[3]
        if batch.dec is not None:
            logits = np.asarray(out.dec_logits, np.float32)
            self.dec_step = (fn, jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args))
            for slot, r in self.eng.active.items():
                if r.state is State.DECODE:
                    self.rows[(r.rid, len(r.output))] = logits[slot]
        if batch.pf is not None:
            logits = np.asarray(out.pf_logits, np.float32)
            toks = np.asarray(batch.pf.tokens)
            lens = np.asarray(batch.pf.length)
            cached = (np.asarray(batch.pf.cached_len)
                      if batch.pf.cached_len is not None
                      else np.zeros_like(lens))
            for i in range(len(lens)):
                c, n = int(cached[i]), int(lens[i])
                for r in self.eng.all_requests:
                    if (r.state is State.PREFILL and r.prefilled == c
                            and r.prompt_len == c + n
                            and np.array_equal(r.prompt[c:], toks[i, :n])):
                        self.rows[(r.rid, 0)] = logits[i]
        return res


def compile_seconds():
    """Returns a reader of the XLA backend compile seconds spent in this
    process since the call."""
    total = [0.0]

    def listen(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            total[0] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: total[0]


def rel_err(a: np.ndarray, ref: np.ndarray) -> float:
    return float(np.abs(a - ref).max() / max(np.abs(ref).max(), 1e-30))


def serve(model, reqs, trainer_rows=None):
    """Run copies of ``reqs`` through a new engine; with ``trainer_rows`` a
    trainer fine-tunes ``TRAINED`` in the same steps.  Returns (engine,
    recorder, trainer or None, wall seconds)."""
    from repro.core.flow import FlowConfig
    from repro.data import datasets
    from repro.serving.engine import EngineConfig, UnifiedEngine
    from repro.training.trainer import MixedLoraTrainer, TrainerConfig
    # A fine-tune step keeps backward residuals for every token it carries,
    # inference rows included: compiled for a v5e, the step with 4x128
    # fine-tune and 4x512 prefill tokens needs 13.9 GiB of temporaries
    # beside 9.1 GiB of arguments, and with 2x128 of each 4.6 GiB.
    # Chunked prefill caps a step's prefill at 128 tokens, and one bucket
    # of 2 rows x 128 tokens keeps the compiled shapes few.
    ecfg = EngineConfig(capacity=8, pf_capacity=2, s_max=1024,
                        virtual_time=False, prefill_chunk=128,
                        flow=FlowConfig(row_buckets=(2,), seq_buckets=(128,)))
    eng = UnifiedEngine(model, ecfg)
    rec = LogitRecorder(eng)
    for r in reqs:
        eng.submit(copy.deepcopy(r))
    tr = None
    if trainer_rows is not None:
        tr_rows, ev_rows = datasets.split_eval(trainer_rows)
        tr = MixedLoraTrainer(TRAINED, model.store.slot_of(TRAINED), tr_rows,
                              ev_rows, TrainerConfig(rows_per_micro=2,
                                                     accum_steps=2, epochs=1))
        eng.add_trainer(tr)
    t0 = time.perf_counter()
    eng.run(max_ticks=100000)
    return eng, rec, tr, time.perf_counter() - t0


def check_served(cfg, eng, reqs, label: str) -> dict:
    """Every request finished with all its tokens, each in the vocab."""
    from repro.serving.request import State
    done = {r.rid: r for r in eng.finished}
    if sorted(done) != [r.rid for r in reqs]:
        raise SmokeFailure(f"{label}: finished {sorted(done)} of "
                           f"{len(reqs)} requests")
    for r in reqs:
        d = done[r.rid]
        if d.state is not State.DONE or len(d.output) != r.max_new_tokens:
            raise SmokeFailure(f"{label}: request {r.rid} ended {d.state} "
                               f"with {len(d.output)} tokens")
        if not all(0 <= t < cfg.vocab for t in d.output):
            raise SmokeFailure(f"{label}: request {r.rid} emitted a token "
                               f"outside the vocab: {d.output}")
    return {rid: list(d.output) for rid, d in done.items()}


def run(cfg, *, kernel_mode: str = "tpu") -> dict:
    """Phases A, A' and B on ``cfg``.  ``kernel_mode`` is phase B's
    ``REPRO_PAGED_ATTN_KERNEL``.  Raises SmokeFailure on a failed check;
    returns the numbers each phase printed."""
    from repro.core.lora import LoRAConfig
    from repro.core.virtualization import AdapterStore, MixedLoraModel
    from repro.data import datasets
    from repro.models.model import init_cache, unified_forward
    from repro.models.schema import init_params
    from repro.models.stream import PFBatch, UnifiedBatch

    compiled = compile_seconds()
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(cfg, jax.random.PRNGKey(SEED)))
    t1 = time.perf_counter()
    store = AdapterStore(cfg, LoRAConfig(n_slots=4, r=8),
                         jax.random.PRNGKey(SEED + 1))
    for i, name in enumerate(SERVED + (TRAINED,)):
        store.load_random(name, jax.random.PRNGKey(100 + i))
    model = MixedLoraModel(cfg, params, store)
    jax.block_until_ready(store.bank)
    print(f"setup: weights made in {t1 - t0:.1f} s, 4 adapters in "
        f"{time.perf_counter() - t1:.1f} s, {compiled():.1f} s of it "
        f"compiling")
    reqs = make_requests(cfg)
    res = {}

    # ---- A: co-serving on the default path --------------------------------
    c0 = compiled()
    ft_rows = datasets.alpaca_like(24, vocab=cfg.vocab, seed=SEED)
    eng, rec_a, tr, wall = serve(model, reqs, trainer_rows=ft_rows)
    out_a = check_served(cfg, eng, reqs, "A")
    losses = np.asarray(tr.train_losses, np.float64)
    if tr.optimizer_steps < 2 or not len(losses) \
            or not np.isfinite(losses).all():
        raise SmokeFailure(f"A: fine-tune took {tr.optimizer_steps} "
                           f"optimizer steps, losses {losses}")
    m = eng.metrics
    res["A"] = dict(wall_s=wall, compile_s=compiled() - c0, steps=m.steps,
                    step_shapes_compiled=rec_a.shapes_compiled,
                    prompt_tokens_computed=m.prefill_tokens,
                    prompt_tokens_reused=m.reused_prefix_tokens,
                    decode_tokens=m.decode_tokens,
                    finetune_tokens=tr.tokens_trained,
                    optimizer_steps=tr.optimizer_steps,
                    loss_first=float(losses[0]), loss_last=float(losses[-1]))
    print(f"A co-serve: {json.dumps(res['A'])}")
    rows_a, bs = rec_a.rows, eng.cachemgr.block_size
    del eng, rec_a, tr           # free A's KV pool and optimizer state

    # ---- A': first-token logits against a plain unbatched forward ---------
    # Two controls the check must catch: the same prompt read with another
    # adapter, and with its block 1 holding another request's first block
    # (what a wrong block-table entry feeds attention).
    r0 = reqs[0]
    L = r0.prompt_len
    fwd = jax.jit(functools.partial(unified_forward, cfg))

    def first_logits(prompt, adapter):
        batch = UnifiedBatch(pf=PFBatch(
            tokens=jnp.asarray(prompt[None]), length=jnp.asarray([L]),
            adapter=jnp.asarray([store.slot_of(adapter)], jnp.int32)))
        out = fwd(params, batch, init_cache(cfg, 1, L), loras=store.bank,
                  lora_scale=store.scale)
        return np.asarray(out.pf_logits[0], np.float32)

    wrong_block = r0.prompt.copy()
    wrong_block[bs:2 * bs] = reqs[1].prompt[:bs]
    got = rows_a[(r0.rid, 0)]
    ref = first_logits(r0.prompt, r0.adapter)
    err = rel_err(got, ref)
    controls = {
        "wrong_adapter": rel_err(got, first_logits(r0.prompt,
                                                   reqs[1].adapter)),
        "wrong_block": rel_err(got, first_logits(wrong_block, r0.adapter))}
    res["A_ref"] = dict(rid=r0.rid, prompt_len=L, rel_err=err,
                        rtol=LOGIT_RTOL, control_rel_err=controls,
                        argmax_equal=bool(ref.argmax() == out_a[r0.rid][0]))
    print(f"A' reference: {json.dumps(res['A_ref'])}")
    if not err <= LOGIT_RTOL:
        raise SmokeFailure(f"A': first-token logits of request {r0.rid} "
                           f"differ from the unbatched forward by {err:.4f}")
    blind = [k for k, e in controls.items() if not e > LOGIT_RTOL]
    if blind:
        raise SmokeFailure(f"A': the {LOGIT_RTOL} logit tolerance does not "
                           f"catch the control faults {blind}: {controls}")

    # ---- B: compiled paged-attention kernels, no trainer -------------------
    c0 = compiled()
    prev = os.environ.get("REPRO_PAGED_ATTN_KERNEL")
    os.environ["REPRO_PAGED_ATTN_KERNEL"] = kernel_mode
    try:
        eng_b, rec_b, _, wall = serve(model, reqs)
        # re-traced under the same kernel mode the engine's steps saw
        fn, shapes = rec_b.dec_step
        kernels = fn.lower(*shapes).as_text().count("tpu_custom_call")
    finally:
        if prev is None:
            os.environ.pop("REPRO_PAGED_ATTN_KERNEL")
        else:
            os.environ["REPRO_PAGED_ATTN_KERNEL"] = prev
    out_b = check_served(cfg, eng_b, reqs, "B")
    differ, worst = [], 0.0
    for r in reqs:
        a, b = out_a[r.rid], out_b[r.rid]
        if a != b:
            k = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
            differ.append((r.rid, k))
            worst = max(worst, rel_err(rec_b.rows[(r.rid, k)],
                                       rows_a[(r.rid, k)]))
    m = eng_b.metrics
    res["B"] = dict(wall_s=wall, compile_s=compiled() - c0, steps=m.steps,
                    step_shapes_compiled=rec_b.shapes_compiled,
                    prompt_tokens_computed=m.prefill_tokens,
                    prompt_tokens_reused=m.reused_prefix_tokens,
                    decode_tokens=m.decode_tokens,
                    kernel_calls_in_decode_step=kernels,
                    requests_differing=len(differ), first_diffs=differ,
                    worst_rel_err_at_diff=worst, rtol=LOGIT_RTOL)
    print(f"B kernels: {json.dumps(res['B'])}")
    if kernel_mode == "tpu" and kernels == 0:
        raise SmokeFailure("B: the decode step holds no Pallas kernel")
    if worst > LOGIT_RTOL:
        raise SmokeFailure(f"B: tokens differ from A at {differ} with "
                           f"logits apart by {worst:.4f}")
    return res


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    cfg = smoke_config()
    print(f"jax {jax.__version__}, device {dev.device_kind} x "
          f"{len(jax.devices())}, compile cache {cache}")
    print(f"config {cfg.name}: d_model {cfg.d_model}, heads {cfg.n_heads}/"
          f"{cfg.n_kv_heads} x {cfg.hd}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
          f"{cfg.dtype}; cut: {cfg.n_layers} of 32 layers (one stage of a "
          f"two-chip pipeline), random weights from seed {SEED}")
    t0 = time.perf_counter()
    run(cfg)
    stats = dev.memory_stats() or {}
    print(f"peak device memory {stats.get('peak_bytes_in_use', 0) / 2**30:.2f}"
          f" GiB of {stats.get('bytes_limit', 0) / 2**30:.2f} GiB; "
          f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
