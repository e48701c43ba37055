"""Fine-tuning driver: concurrent multi-LoRA training through the unified
runtime.

  PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \\
      --adapters 2 --epochs 2

Full-size configs are for real TPU slices (pair with launch/mesh.py); on this
CPU container always pass ``--reduced``.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.core.lora import LoRAConfig
from repro.core.virtualization import AdapterStore, MixedLoraModel
from repro.data import datasets
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import EngineConfig, UnifiedEngine
from repro.training.trainer import MixedLoraTrainer, TrainerConfig


def make_aux(cfg, rng):
    if cfg.encoder is not None:
        return rng.standard_normal((cfg.encoder.n_frames, cfg.d_model),
                                   dtype=np.float32) * 0.1
    if cfg.cross_attn_every:
        return rng.standard_normal((cfg.n_img_tokens, cfg.d_model),
                                   dtype=np.float32) * 0.1
    return None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--adapters", type=int, default=2)
    ap.add_argument("--rows", type=int, default=48)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--accum", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    from repro.models.schema import init_params
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    lcfg = LoRAConfig(n_slots=max(4, args.adapters), r=8)
    store = AdapterStore(cfg, lcfg, jax.random.PRNGKey(args.seed + 1))
    model = MixedLoraModel(cfg, params, store)
    eng = UnifiedEngine(model, EngineConfig(capacity=2, pf_capacity=2,
                                            s_max=max(256, 2)))
    rng = np.random.default_rng(args.seed)
    aux = make_aux(cfg, rng)

    gens = [datasets.alpaca_like, datasets.gsm8k_like]
    for i in range(args.adapters):
        name = f"adapter{i}"
        store.load_random(name, jax.random.PRNGKey(100 + i))
        rows = gens[i % 2](args.rows, vocab=cfg.vocab, seed=args.seed + i)
        tr_rows, ev_rows = datasets.split_eval(rows)
        eng.add_trainer(MixedLoraTrainer(
            name, store.slot_of(name), tr_rows, ev_rows,
            TrainerConfig(rows_per_micro=2, accum_steps=args.accum,
                          epochs=args.epochs), aux_embed=aux))

    t0 = time.time()
    metrics = eng.run(max_ticks=100000)
    dt = time.time() - t0
    print(f"arch={cfg.name} adapters={args.adapters} wall={dt:.1f}s "
          f"rates={metrics.rates()}")
    for name, tr in eng.trainers.items():
        first = np.mean(tr.train_losses[:4]) if tr.train_losses else float("nan")
        last = np.mean(tr.train_losses[-4:]) if tr.train_losses else float("nan")
        print(f"  {name}: loss {first:.3f} -> {last:.3f}  "
              f"opt_steps={tr.optimizer_steps} "
              f"eval={np.mean(tr.eval_losses[-4:]) if tr.eval_losses else float('nan'):.3f} "
              f"tokens={tr.tokens_trained}")


if __name__ == "__main__":
    main()
