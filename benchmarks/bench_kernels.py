"""Kernel-level microbenches.

Part 1 — SMLM: ONE batched multi-LoRA call (the SMLM design) vs the
traditional serial per-adapter loop the paper replaces (Section 3.3).
Measured with the jnp oracle on CPU (the Pallas kernel targets TPU); also
reports kernel-invocation counts, the paper's other win.

Part 2 — paged attention: sequential block-table walk vs the flash-decoding
split-K family (``kernels.splitk``), swept over decode/verify shapes.
Exactness is REAL (both kernels run in interpret mode against each other
and the jnp oracle, same KV pool — equal HBM by construction); throughput
is the occupancy model from ``kernels.autotune`` (waves of concurrent grid
cells), because grid parallelism is not observable on the CPU interpreter —
on a real TPU, pass a wall-clock ``measure`` to ``autotune.sweep``.  The
sweep also populates the autotune table and writes it to ``attn_tune.json``
(load with ``serve.py --attn-tune-file``).

Emits ``BENCH_kernels.json`` for the run.py harness / CI gate.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv
from repro.kernels import autotune, ref
from repro.kernels.decode_attn import paged_attention
from repro.kernels.splitk import paged_verify_attention_splitk


def _serial_loop(x, a, b, ids, n):
    """One matmul pair PER ADAPTER (masked) — 2n kernel calls."""
    y = jnp.zeros((x.shape[0], b.shape[-1]), x.dtype)
    for i in range(n):
        m = (ids == i)[:, None].astype(x.dtype)
        y = y + ((x * m) @ a[i]) @ b[i]
    return y


def _bench(fn, *args, iters=20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.monotonic()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / iters


def smlm_micro(T: int = 4096, d: int = 512, r: int = 8, o: int = 512):
    for n in (2, 4, 8):
        ks = jax.random.split(jax.random.PRNGKey(n), 4)
        x = jax.random.normal(ks[0], (T, d))
        a = jax.random.normal(ks[1], (n, d, r))
        b = jax.random.normal(ks[2], (n, r, o))
        ids = jax.random.randint(ks[3], (T,), 0, n)
        scale = jnp.ones((T,))
        batched = jax.jit(lambda x, a, b, i: ref.bgmv_ref(x, a, b, i, scale))
        serial = jax.jit(lambda x, a, b, i: _serial_loop(x, a, b, i, n))
        tb = _bench(batched, x, a, b, ids)
        ts = _bench(serial, x, a, b, ids)
        csv(f"kernels/smlm_batched_n{n}", tb * 1e6,
            f"serial_us={ts * 1e6:.0f};speedup={ts / tb:.2f}x;"
            f"kernel_calls=1_vs_{2 * n}")


# --------------------------------------------------- split-K attention sweep

# decode arms: (B, h, g, hd, bs, nbt).  The long-context/small-batch arm is
# the one flash decoding exists for (the B*g grid cells alone cannot fill
# the lanes); the batched arm shows the heuristic correctly declining to
# split.
ARMS = {
    "long_ctx_small_batch": dict(B=1, h=4, g=2, hd=64, bs=16, nbt=32),
    "long_ctx_batched": dict(B=8, h=4, g=2, hd=64, bs=16, nbt=32),
    "short_ctx_small_batch": dict(B=2, h=4, g=2, hd=32, bs=16, nbt=4),
}


def _paged_problem(B, h, g, hd, bs, nbt, seed=0, Sq=0):
    """Random pool + scattered non-contiguous tables + ragged positions."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    n_blocks = nbt * B + 2
    k_pool = jax.random.normal(ks[0], (n_blocks, bs, g, hd))
    v_pool = jax.random.normal(ks[1], (n_blocks, bs, g, hd))
    rng = np.random.default_rng(seed)
    span = nbt * bs - max(Sq, 1)
    pos = np.array([span - 1 - rng.integers(0, max(span // 3, 1))
                    for _ in range(B)], np.int64)
    tables = np.zeros((B, nbt), np.int32)
    for b in range(B):
        need = (pos[b] + max(Sq, 1)) // bs + 1
        tables[b, :need] = rng.choice(np.arange(1, n_blocks), size=need,
                                      replace=False)
    qshape = (B, h, hd) if Sq == 0 else (B, Sq, h, hd)
    q = jax.random.normal(ks[2], qshape)
    return q, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(pos, jnp.int32)


def _allclose(a, b, tol=2e-5):
    return bool(np.allclose(np.asarray(a, np.float32),
                            np.asarray(b, np.float32), rtol=tol, atol=tol))


def _arm_result(name, spec):
    B, h, g, hd, bs, nbt = (spec[k] for k in ("B", "h", "g", "hd", "bs",
                                              "nbt"))
    bh = B * g                   # grid cells per split: (request, KV head)
    cfg = autotune.choose(hd, bs, nbt, bh)
    ns = cfg.num_splits

    # decode exactness: split-K vs the sequential kernel vs the jnp oracle,
    # over the SAME pool (equal HBM — split-K adds only O(ns*B*h*hd) fp32
    # partials, transient epilogue traffic, not pool residency)
    q, kp, vp, tbl, pos = _paged_problem(B, h, g, hd, bs, nbt, seed=hash(name) % 1000)
    ones = jnp.ones((B,), jnp.int32)
    y_seq = paged_attention(q[:, None], kp, vp, tbl, pos, ones,
                            interpret=True)[:, 0]
    y_spl = paged_verify_attention_splitk(q[:, None], kp, vp, tbl, pos, ones,
                                          num_splits=max(ns, 2),
                                          interpret=True)[:, 0]
    y_ref = ref.paged_decode_ref(q, kp, vp, tbl, pos)
    exact = (_allclose(y_spl, y_seq) and _allclose(y_spl, y_ref))

    # verify-chunk exactness on the same geometry (Sq = 4, ragged lens)
    Sq = 4
    qv, kpv, vpv, tblv, posv = _paged_problem(B, h, g, hd, bs, nbt, seed=7,
                                              Sq=Sq)
    lens = jnp.asarray(np.random.default_rng(7).integers(1, Sq + 1, B),
                       jnp.int32)
    yv_seq = paged_attention(qv, kpv, vpv, tblv, posv, lens, interpret=True)
    yv_spl = paged_verify_attention_splitk(qv, kpv, vpv, tblv, posv, lens,
                                           num_splits=max(ns, 2),
                                           interpret=True)
    exact = exact and _allclose(yv_spl, yv_seq, tol=3e-5)

    t_seq = autotune.modeled_grid_time(bh, nbt, 1)
    t_spl = autotune.modeled_grid_time(bh, nbt, ns)
    speedup = t_seq / t_spl
    csv(f"kernels/splitk_{name}", t_spl,
        f"seq_t={t_seq:.2f};num_splits={ns};speedup={speedup:.2f}x;"
        f"exact={exact}")
    return {"B": B, "h": h, "bh": bh, "hd": hd, "bs": bs, "nbt": nbt,
            "num_splits": ns, "exact": exact,
            "seq_modeled_t": t_seq, "splitk_modeled_t": t_spl,
            "speedup": round(speedup, 3), "equal_hbm": True,
            "pool_bytes": int(kp.size * kp.dtype.itemsize * 2)}


def splitk_sweep():
    arms = {name: _arm_result(name, spec) for name, spec in ARMS.items()}

    # populate + persist the autotune table for the swept shapes (occupancy
    # model on CPU; a TPU run passes measure= for wall-clock scoring)
    shapes = [(s["hd"], s["bs"], s["nbt"], s["bh"]) for s in arms.values()]
    autotune.sweep(shapes)
    n_entries = autotune.save_table("attn_tune.json")

    long_ctx = arms["long_ctx_small_batch"]
    doc = {
        "exact": all(a["exact"] for a in arms.values()),
        "arms": arms,
        "long_ctx": {"nbt": long_ctx["nbt"], "speedup": long_ctx["speedup"],
                     "num_splits": long_ctx["num_splits"],
                     "equal_hbm": long_ctx["equal_hbm"],
                     "exact": long_ctx["exact"]},
        "tuned_entries": n_entries,
        "tuning_table": "attn_tune.json",
        "throughput_model": "autotune.modeled_grid_time (occupancy waves); "
                            "exactness is measured, interpret-mode kernels",
    }
    with open("BENCH_kernels.json", "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    csv("kernels/splitk_long_ctx", long_ctx["splitk_modeled_t"],
        f"speedup={long_ctx['speedup']:.2f}x;"
        f"num_splits={long_ctx['num_splits']};exact={doc['exact']}")


def main(T: int = 4096, d: int = 512, r: int = 8, o: int = 512):
    smlm_micro(T, d, r, o)
    splitk_sweep()


if __name__ == "__main__":
    main()
