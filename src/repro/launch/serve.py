"""Serving driver: multi-LoRA inference (optionally co-running fine-tuning)
through the unified runtime.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --reduced \\
      --rps 2 --requests 40 --adapters 2 [--finetune]
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_reduced
from repro.core.lora import LoRAConfig
from repro.core.virtualization import AdapterStore, MixedLoraModel
from repro.data import datasets, workload
from repro.launch.compile_cache import enable_compile_cache
from repro.serving.engine import EngineConfig, UnifiedEngine
from repro.serving.request import PRIORITY_CLASSES, Request
from repro.serving.slo import SLOConfig, slo_attainment
from repro.training.trainer import MixedLoraTrainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="llama3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--adapters", type=int, default=2)
    ap.add_argument("--requests", type=int, default=40)
    ap.add_argument("--rps", type=float, default=2.0)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--finetune", action="store_true")
    ap.add_argument("--spec", type=int, default=0, metavar="K",
                    help="speculative decoding with up to K drafted tokens "
                         "per step (prompt-lookup drafter; exact greedy)")
    ap.add_argument("--wall-clock", action="store_true",
                    help="real time instead of the calibrated virtual clock")
    ap.add_argument("--prefill-chunk", type=int, default=0, metavar="T",
                    help="per-tick prefill-token budget: long prompts "
                         "prefill as bounded chunks co-batched with decode "
                         "(0 = unchunked)")
    ap.add_argument("--adapter-paging", default=False,
                    action=argparse.BooleanOptionalAction,
                    help="page adapter weights through the KV block pool "
                         "(unified memory: HBM flows between cache capacity "
                         "and adapter residency; scheduler prefers resident-"
                         "adapter waiters and co-batches same-adapter "
                         "requests).  Implies a small LRU adapter bank so "
                         "residency actually pages; default off = static "
                         "bank partition")
    ap.add_argument("--no-hash-dedup", action="store_true",
                    help="disable content-hash KV block dedup (and the "
                         "prefix-aware admission that rides on it): every "
                         "request recomputes and re-stores its whole prompt")
    ap.add_argument("--paged-attn-kernel", default=None,
                    choices=["off", "interpret", "tpu", "splitk",
                             "splitk-interpret"],
                    help="paged attention backend (sets "
                         "REPRO_PAGED_ATTN_KERNEL): off = jnp gather view; "
                         "interpret/tpu = sequential Pallas kernels; "
                         "splitk[-interpret] = flash-decoding split-K "
                         "decode/verify with autotuned fan-out")
    ap.add_argument("--attn-tune-file", default=None, metavar="PATH",
                    help="JSON tuning table for the paged-attention kernel "
                         "family (written by benchmarks/bench_kernels.py); "
                         "shapes it misses fall back to the deterministic "
                         "heuristic")
    ap.add_argument("--over-admit", type=float, default=1.0, metavar="F",
                    help="KV reservation lending factor >= 1.0: the gate "
                         "charges only 1/F of outstanding reservation debt "
                         "and preempts (recompute) when lending comes due "
                         "(1.0 = conservative gate)")
    ap.add_argument("--kv-host-blocks", type=int, default=0, metavar="N",
                    help="tiered KV memory: host-side block pool sized to N "
                         "device blocks' worth of host RAM.  Preemption "
                         "victims swap out D2H (and restore H2D at "
                         "re-admission) when the modeled transfer beats "
                         "suffix recompute, and shed hash-index blocks "
                         "demote to the host tier instead of being dropped "
                         "(0 = recompute-only preemption)")
    ap.add_argument("--kv-host-quant", action="store_true",
                    help="int8-quantize host-tier KV residency (~2x host "
                         "capacity at equal budget).  NOT bit-exact: "
                         "restored K/V is dequantized, so outputs may "
                         "differ from the recompute path")
    ap.add_argument("--priority", default="standard",
                    choices=["interactive", "standard", "batch", "mixed"],
                    help="request priority class: interactive is preempted "
                         "last and never lends its KV reservation; batch is "
                         "preempted first and lends first under "
                         "--over-admit; mixed round-robins the three "
                         "classes across requests")
    ap.add_argument("--replicas", type=int, default=1, metavar="N",
                    help="in-process engine replicas behind one router "
                         "(shared base weights, per-replica KV pools and "
                         "adapter banks, fleet-wide block index with "
                         "remote prefix fetch); 1 = single engine")
    ap.add_argument("--router", default="affinity",
                    choices=["affinity", "round-robin", "least-loaded"],
                    help="replica placement policy (--replicas > 1): "
                         "affinity scores resident prefix + adapter "
                         "residency against queue depth; the others are "
                         "locality-blind baselines")
    ap.add_argument("--no-remote-fetch", action="store_true",
                    help="never copy prefix blocks between replica pools "
                         "(independent replicas with local dedup only)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.paged_attn_kernel is not None:
        import os
        os.environ["REPRO_PAGED_ATTN_KERNEL"] = (
            "" if args.paged_attn_kernel == "off" else args.paged_attn_kernel)
    if args.attn_tune_file:
        from repro.kernels.autotune import load_table
        n = load_table(args.attn_tune_file)
        print(f"attn autotune: loaded {n} entries from {args.attn_tune_file}")

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    from repro.models.schema import init_params
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    # under unified paging the bank is a small staging tier (adapters page
    # in/out of the shared pool); the static baseline sizes it to hold
    # every adapter, the pre-paging behavior
    n_slots = (max(4, min(args.adapters, 16)) if args.adapter_paging
               else max(4, args.adapters))
    lcfg = LoRAConfig(n_slots=n_slots, r=8)
    store = AdapterStore(cfg, lcfg, jax.random.PRNGKey(args.seed + 1))
    names = []
    ranks = [2, 4, 8]       # heterogeneous true ranks => variable footprints
    for i in range(args.adapters):
        name = f"lora{i}"
        store.load_random(name, jax.random.PRNGKey(100 + i),
                          evict=args.adapter_paging,
                          rank=(ranks[i % 3] if args.adapter_paging
                                else None))
        names.append(name)
    model = MixedLoraModel(cfg, params, store)
    spec = None
    if args.spec > 0:
        from repro.spec import SpecConfig
        spec = SpecConfig(k_max=args.spec, drafter="ngram")
    ecfg = EngineConfig(
        capacity=8, pf_capacity=4, s_max=256,
        virtual_time=not args.wall_clock, spec=spec,
        prefill_chunk=args.prefill_chunk,
        hash_dedup=not args.no_hash_dedup,
        over_admit=args.over_admit,
        adapter_paging=args.adapter_paging,
        kv_host_blocks=args.kv_host_blocks,
        kv_host_quant=args.kv_host_quant)
    fleet = None
    if args.replicas > 1:
        from repro.fleet import FleetConfig, RouterConfig, build_fleet
        fleet = build_fleet(model, ecfg, FleetConfig(
            replicas=args.replicas,
            router=RouterConfig(policy=args.router),
            remote_fetch=not args.no_remote_fetch))
        eng = fleet.engines[0]
    else:
        eng = UnifiedEngine(model, ecfg)
    if args.over_admit > 1.0 and not eng.paged:
        print("note: --over-admit needs the paged cache; using the "
              "conservative dense layout for this model")
    if args.prefill_chunk and not eng.chunk_budget:
        print("note: --prefill-chunk is inactive for this model "
              "(needs the paged cache and an attention-only pattern)")
    if eng.hash_dedup and not eng.suffix_prefill:
        print("note: hash dedup shares block STORAGE for this model but "
              "suffix-only prefill (compute skip) is inactive "
              "(needs the paged cache and an attention-only pattern)")

    rng = np.random.default_rng(args.seed)
    aux = None
    if cfg.encoder is not None:
        aux = rng.standard_normal((cfg.encoder.n_frames, cfg.d_model),
                                  dtype=np.float32) * 0.1
    elif cfg.cross_attn_every:
        aux = rng.standard_normal((cfg.n_img_tokens, cfg.d_model),
                                  dtype=np.float32) * 0.1

    prompts = datasets.sharegpt_prompts(args.requests, vocab=cfg.vocab,
                                        seed=args.seed)
    arrivals = workload.poisson_arrivals(args.rps, args.requests, args.seed)
    front = fleet if fleet is not None else eng
    classes = (PRIORITY_CLASSES if args.priority == "mixed"
               else (args.priority,))
    for i, (p, t) in enumerate(zip(prompts, arrivals)):
        front.submit(Request(rid=i, prompt=p, adapter=names[i % len(names)],
                             max_new_tokens=args.max_new, arrival=float(t),
                             aux_embed=aux,
                             priority_class=classes[i % len(classes)]))

    if args.finetune:
        rows = datasets.alpaca_like(32, vocab=cfg.vocab, seed=args.seed)
        tr_rows, ev_rows = datasets.split_eval(rows)
        eng.add_trainer(MixedLoraTrainer(
            names[0], store.slot_of(names[0]), tr_rows, ev_rows,
            TrainerConfig(rows_per_micro=2, accum_steps=4, epochs=1),
            aux_embed=aux))

    m = front.run(max_ticks=500000)
    finished = (eng.finished if fleet is None
                else [r for e in fleet.engines for r in e.finished])
    att = slo_attainment(finished, SLOConfig())
    print(f"arch={cfg.name} requests={args.requests} rps={args.rps} "
          f"finished={len(finished)} SLO={att:.3f}")
    print(f"rates={m.rates()}")
    if fleet is not None:
        print(f"fleet: replicas={args.replicas} router={args.router} "
              f"routed={fleet.routed} "
              f"remote_fetch_blocks={m.remote_fetch_blocks} "
              f"remote_fetch_time={m.remote_fetch_time:.4f} "
              f"fleet_index_keys={len(fleet.index)}")
    if args.spec > 0:
        drafted = (m.spec_drafted if fleet is None
                   else sum(e.spec_drafted for e in m.per_engine))
        accepted = (m.spec_accepted if fleet is None
                    else sum(e.spec_accepted for e in m.per_engine))
        print(f"spec: drafted={drafted} accepted={accepted} "
              f"acceptance={accepted / max(drafted, 1):.2f} steps={m.steps}")
    def tot(field, agg=sum):
        # fleet rollup carries the headline counters; per-engine Metrics
        # hold the rest — aggregate either way
        if fleet is not None:
            return agg(getattr(e, field) for e in m.per_engine)
        return getattr(m, field)

    if args.over_admit > 1.0 or tot("preemptions"):
        print(f"over-admit: factor={args.over_admit} "
              f"preemptions={tot('preemptions')} "
              f"recomputed={tot('preempted_tokens_recomputed')} "
              f"lent_peak={tot('lent_blocks_peak', max)}")
    if m.reused_prefix_tokens or args.prefill_chunk:
        print(f"prefix: reused={m.reused_prefix_tokens} "
              f"computed={m.prefill_tokens} "
              f"max_pf_step={tot('max_pf_tokens_step', max)}")
    if args.adapter_paging or tot("adapter_swap_ins"):
        print(f"adapters: swap_ins={tot('adapter_swap_ins')} "
              f"swap_in_bytes={tot('adapter_swap_in_bytes')} "
              f"resident_hits={tot('adapter_resident_hits')} "
              f"blocks_resident={tot('adapter_blocks_resident')} "
              f"peak_coresident={tot('adapter_peak_coresident', max)}")
    if args.kv_host_blocks > 0:
        print(f"kv-tiers: host_blocks={args.kv_host_blocks} "
              f"quant={args.kv_host_quant} "
              f"swap_outs={tot('kv_swap_outs')} "
              f"restores={tot('kv_restores')} "
              f"skips={tot('kv_swap_skips')} "
              f"demotions={tot('kv_demotions')} "
              f"rehydrated={tot('kv_rehydrated_blocks')} "
              f"host_peak_bytes={tot('host_bytes_peak', max)}")
    if eng.hash_dedup:
        print(f"dedup: hash_hits={m.hash_hits} "
              f"resident_blocks={tot('hash_blocks_resident')} "
              f"probe_admissions={tot('probe_admissions')}")
    if args.finetune:
        tr = eng.trainers[names[0]]
        print(f"finetune: tokens={tr.tokens_trained} "
              f"opt_steps={tr.optimizer_steps}")


if __name__ == "__main__":
    main()
