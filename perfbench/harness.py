"""One run of one cell: load its files by name, build the engine from the
seed, warm every step shape the cell uses, pre-roll, drive
``UnifiedEngine.tick()`` on the wall clock for the window, read the
metrics, then check what the window served against the float32 reference.

Everything that belongs to one cell, configuration, traffic mix or metric
is a file found by the name ``BENCHMARK.json`` gives it:

    perfbench/cells/<cell>.json      engine sizes, fixed rate, limits
    <configs[].file>                 the model, as run (published keys)
    perfbench/arch/<model_type>.py   the configuration's architecture, by
                                     the ``model_type`` its file states:
                                     ``model_config``, ``Reference``,
                                     ``step_flops``, ``NORMS``
    perfbench/traffic/<mix>.json     the mix's parameters
    perfbench/metrics/<metric>.py    a reader ``read(run, name)``; a name
                                     ``a.b`` falls back to ``a.py``
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import reference as ref_mod
from perfbench import stats
from perfbench import traffic as traffic_mod
from perfbench import weights

HERE = Path(__file__).resolve().parent
REPO = HERE.parent

TRACE_DIR = HERE / ".trace"
TRAINED = "ft"


# -- the cell's files ---------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict          # the configuration file (published keys, as run)
    arch: ModuleType      # perfbench/arch/<model_type>.py
    traffic: Dict         # the mix's parameters
    setup: Dict           # perfbench/cells/<name>.json
    metrics: List[Dict]   # manifest entries that this cell reports


def load_cell(name: str, root: Path = REPO) -> Cell:
    man = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg_file = {c["name"]: c["file"] for c in man["configs"]}[w["config"]]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    metrics = ([dict(m, kind="end_to_end") for m in man["end_to_end"]
                if applies(m)]
               + [dict(m, kind="per_layer") for m in man["per_layer"]
                  if applies(m)])
    config = json.loads((root / cfg_file).read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        arch=architecture(config["model_type"], root),
        traffic=json.loads(
            (root / "perfbench" / "traffic" / f"{w['traffic']}.json")
            .read_text()),
        setup=json.loads(
            (root / "perfbench" / "cells" / f"{name}.json").read_text()),
        metrics=metrics)


def architecture(model_type: str, root: Path = REPO) -> ModuleType:
    """The module ``perfbench/arch/<model_type>.py`` under ``root``."""
    rel = f"perfbench/arch/{model_type}.py"
    path = root / rel
    if not path.is_file():
        raise FileNotFoundError(f"model_type {model_type!r} has no "
                                f"architecture module: no {rel}")
    if path.resolve() == (HERE / "arch" / f"{model_type}.py").resolve():
        return importlib.import_module(f"perfbench.arch.{model_type}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_arch_{model_type}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str) -> Optional[Callable]:
    """The metric's reader: ``metrics/<name>.py``, else the file named by
    the name cut at its last dot, and so on."""
    parts = name.split(".")
    while parts:
        path = HERE / "metrics" / (".".join(parts) + ".py")
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                "perfbench_metric_" + "_".join(parts), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
        parts.pop()
    return None


def compile_seconds() -> Callable[[], Tuple[int, float]]:
    """A reader of (backend compiles, seconds) in this process since the
    call."""
    seen = [0, 0.0]

    def listen(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            seen[0] += 1
            seen[1] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    return lambda: (seen[0], seen[1])


# -- the engine, from the seed ------------------------------------------------

@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    kind: Optional[str]   # "fwd" | "grad" | None (no step ran)
    step_s: float
    waiting: int
    batch: object = None  # the step's UnifiedBatch until ``Recorder.sizes``
    flops: float = 0.0


def _slot(tree, slot: int):
    return jax.tree_util.tree_map(lambda x: x[..., slot, :, :], tree)


class Recorder:
    """Wraps the engine's entry points from outside: host-clock times and
    profiler spans (``engine.tick``, ``step.fwd``, ``step.grad``,
    ``step.apply``), the batch each step receives (read on the host once
    the window has closed), and the trained adapter's first ``steps``
    optimizer steps: the grad steps' rows and losses, the adapter before
    the first apply, the optimizer's first moment after it, and the adapter
    after the last."""

    def __init__(self, eng, ft_slot: int, steps: int):
        self.eng, self.ft_slot, self.steps = eng, ft_slot, steps
        self.recording = False
        self.ticks: List[Tick] = []
        self.grad_log: List[Tuple] = []   # (ft batch, loss sums, counts)
        self.bounds: List[int] = []       # len(grad_log) at each apply
        self.p0 = self.m1 = self.pn = None
        self._step = None
        self._tick, self._fwd = eng.tick, eng.forward_step
        self._grad, self._apply = eng.grad_step, eng.apply_step
        eng.tick = self.tick
        eng.forward_step = lambda *a: self.step("fwd", self._fwd, a)
        eng.grad_step = lambda *a: self.step("grad", self._grad, a)
        eng.apply_step = self.apply

    def step(self, kind, fn, args):
        with jax.profiler.TraceAnnotation(f"step.{kind}"):
            t0 = time.perf_counter()
            res = fn(*args)
            jax.block_until_ready(res)
            dt = time.perf_counter() - t0
        self._step = (kind, dt, args[3])
        if kind == "grad" and len(self.bounds) < self.steps:
            self.grad_log.append((args[3].ft, res.out.ft_loss_sum,
                                  res.out.ft_tok_count))
        return res

    def apply(self, grads, state, bank, mask):
        with jax.profiler.TraceAnnotation("step.apply"):
            new_bank, new_state = self._apply(grads, state, bank, mask)
        if len(self.bounds) < self.steps:
            if not self.bounds:
                self.p0 = _slot(bank, self.ft_slot)
                self.m1 = _slot(new_state.m, self.ft_slot)
            self.bounds.append(len(self.grad_log))
            if len(self.bounds) == self.steps:
                self.pn = _slot(new_bank, self.ft_slot)
        return new_bank, new_state

    def tick(self):
        self._step = None
        clock = self.eng.clock
        with jax.profiler.TraceAnnotation("engine.tick"):
            t0 = clock.now()
            busy = self._tick()
            t1 = clock.now()
        if self.recording:
            kind, dt, batch = self._step or (None, 0.0, None)
            self.ticks.append(Tick(t0, t1, kind, dt, len(self.eng.waiting),
                                   batch))
        return busy

    @property
    def stepped(self) -> bool:
        return self._step is not None

    def sizes(self, cell: Cell, r: int):
        """Each recorded tick's model FLOPs (the architecture's
        ``step_flops``), from the host-side sizes of its batch: trained
        fine-tune rows, prefill rows (computed, cached) and decode rows with
        a block table (their context)."""
        for x in self.ticks:
            if x.batch is None:
                continue
            b = jax.device_get(x.batch)
            ft = ([int(n) for n, w in zip(b.ft.mask.sum(1), b.ft.weight)
                   if w > 0] if b.ft is not None else [])
            pf = ([(int(n), 0 if b.pf.cached_len is None
                    else int(b.pf.cached_len[i]))
                   for i, n in enumerate(b.pf.length) if n > 0]
                  if b.pf is not None else [])
            dec = ([int(p) + 1 for p, t in zip(b.dec.pos, b.dec.block_tables)
                    if t.any()] if b.dec is not None else [])
            x.flops = cell.arch.step_flops(cell.config, r, ft, pf, dec)
            x.batch = None

    def training(self, b1: float, targets: Sequence[str]) -> Optional[Dict]:
        """The trained adapter's first optimizer steps as the program took
        them: ``steps`` (per step, the tokens of its rows), ``losses`` (per
        step, per row), ``grad`` (the first gradient as the optimizer got
        it, from its first moment) and ``change``, over the LoRA
        ``targets``; None if the run made fewer steps."""
        if len(self.bounds) < self.steps:
            return None
        steps, losses, lo = [], [], 0
        for hi in self.bounds:
            toks, ls = [], []
            for ft, loss, cnt in jax.device_get(self.grad_log[lo:hi]):
                for i in range(len(ft.weight)):
                    if ft.adapter[i] == self.ft_slot and ft.weight[i] > 0:
                        toks.append(ft.tokens[i][ft.mask[i]])
                        ls.append(float(loss[i]) / max(float(cnt[i]), 1.0))
            steps.append(toks)
            losses.append(np.array(ls))
            lo = hi
        grad = ref_mod.leaves(jax.tree_util.tree_map(
            lambda m: m / (1.0 - b1), self.m1), targets)
        p0, pn = (ref_mod.leaves(self.p0, targets),
                  ref_mod.leaves(self.pn, targets))
        return dict(steps=steps, losses=losses, grad=grad,
                    change={k: pn[k] - p0[k] for k in p0})


def drive(eng, rec: Recorder, until: float):
    """Tick until the engine's clock passes ``until``; sleep to the next
    arrival when a tick had nothing to run."""
    while eng.clock.now() < until:
        eng.tick()
        if not rec.stepped:
            nxt = eng.future[0].arrival if eng.future else until
            wait = min(nxt, until) - eng.clock.now()
            if wait > 0:
                time.sleep(wait)


def adapter_shapes(cfg, lcfg, n: int):
    """Pytree of adapter factors, stacked over ``n`` adapters on axis 0."""
    from repro.models.schema import lora_targets
    dt = jnp.dtype(cfg.dtype)
    return jax.tree_util.tree_map(
        lambda t: {"a": jax.ShapeDtypeStruct((n, *t.stack, t.d_in, lcfg.r),
                                             dt),
                   "b": jax.ShapeDtypeStruct((n, *t.stack, lcfg.r, t.d_out),
                                             dt)},
        lora_targets(cfg, lcfg.targets),
        is_leaf=lambda x: hasattr(x, "d_in"))


def makers(cell: Cell, cfg, lcfg, n_adapters: int):
    from repro.models.schema import abstract_params
    lo = cell.config["lora"]
    b_scale = 0.02 * lo["alpha"] / lo["r"]
    return (weights.maker(abstract_params(cfg),
                          weights.weight_std(cell.arch.NORMS)),
            weights.maker(adapter_shapes(cfg, lcfg, n_adapters),
                          weights.adapter_std(b_scale)))


def lora_config(cell: Cell, n_slots: int):
    from repro.core.lora import LoRAConfig
    lo = cell.config["lora"]
    return LoRAConfig(n_slots=n_slots, r=lo["r"], alpha=float(lo["alpha"]),
                      targets=tuple(lo["targets"]))


def engine_config(cell: Cell):
    from repro.core.flow import FlowConfig
    from repro.serving.engine import EngineConfig
    from repro.serving.scheduler import SchedulerConfig
    from repro.training.optimizer import AdamWConfig
    e = cell.setup["engine"]
    t = cell.traffic.get("trainer")
    return EngineConfig(
        opt=AdamWConfig(**t["optimizer"]) if t else AdamWConfig(),
        capacity=e["capacity"], pf_capacity=e["pf_capacity"],
        s_max=e["s_max"], virtual_time=False,
        prefill_chunk=e["prefill_chunk"], block_size=e["block_size"],
        n_blocks=e["n_blocks"],
        scheduler=SchedulerConfig(max_prefill_per_tick=e["pf_capacity"]),
        flow=FlowConfig(row_buckets=tuple(e["row_buckets"]),
                        seq_buckets=tuple(e["seq_buckets"])))


TRAIN_STEPS = 3   # optimizer steps of the trainer that the reference follows


def step_shapes(cell: Cell, ecfg) -> Dict[str, List[Tuple[int, int]]]:
    """Every (rows, sequence) bucket the cell's traffic can assemble."""
    from repro.core import flow
    fc, mix = ecfg.flow, cell.traffic

    def buckets(n_rows, lo, hi):
        rows = sorted({flow.bucket(k, fc.row_buckets)
                       for k in range(1, n_rows + 1)})
        seqs = sorted({flow.bucket(n, fc.seq_buckets)
                       for n in range(lo, hi + 1)})
        return [(b, s) for b in rows for s in seqs]

    longest = mix["system_prompt_tokens"] + mix["user_tokens"]["max"]
    pf_hi = min(ecfg.prefill_chunk, longest) if ecfg.prefill_chunk \
        else longest
    out = {"pf": buckets(ecfg.pf_capacity, 1, pf_hi), "ft": []}
    if "trainer" in mix:
        t = mix["trainer"]
        out["ft"] = buckets(t["rows_per_micro"], t["tokens"]["min"],
                            t["tokens"]["max"])
    return out


def warm(eng, cell: Cell, rec: Recorder) -> int:
    """Run every step program the cell can use once, on inert rows (null
    KV block, padding adapter), and the optimizer path's programs, keeping
    none of their results.  Returns the number of step programs."""
    from repro.core.flow import FTRow, PFReq, assemble
    from repro.serving.kvcache import _copy_block
    from repro.training.optimizer import tree_add, tree_mask_slots
    e, mgr, store = eng.ecfg, eng.cachemgr, eng.model.store
    shapes = step_shapes(cell, e)
    ft_opts = [None] + shapes["ft"]
    pf_opts = [None] + [(b, s, c) for b, s in shapes["pf"]
                        for c in (None, 0)]
    n = 0
    grads = None
    for ft in ft_opts:
        for pf in pf_opts:
            for dec in (False, True):
                if ft is None and pf is None and not dec:
                    continue
                ft_rows = [] if ft is None else [
                    FTRow(tokens=np.zeros(ft[1], np.int32),
                          labels=np.zeros(ft[1], np.int32),
                          slot=rec.ft_slot, weight=0.0)] * ft[0]
                pf_reqs = [] if pf is None else [
                    PFReq(tokens=np.zeros(pf[1], np.int32), slot=-1,
                          block_table=np.zeros(mgr.nbt, np.int32),
                          cached_len=pf[2])] * pf[0]
                c = e.capacity if dec else 0
                batch = assemble(
                    ft_rows, pf_reqs, np.zeros(c, np.int64),
                    np.zeros(c, np.int64), np.full(c, -1, np.int64), e.flow,
                    dec_tables=(np.zeros((c, mgr.nbt), np.int32) if dec
                                else None))
                fn = rec._grad if ft_rows else rec._fwd
                res = fn(eng.model.base, store.bank, store.scale, batch,
                         mgr.step_cache() if pf or dec else None)
                jax.block_until_ready(res)
                if ft_rows:
                    grads = res.grads
                del res
                n += 1
    if grads is not None:
        mask = store.slot_mask([TRAINED])
        jax.block_until_ready(rec._apply(grads, eng.opt_state, store.bank,
                                         mask))
        jax.block_until_ready(tree_mask_slots(tree_add(eng.grad_accum, grads),
                                              1.0 - mask))
        jax.block_until_ready((_slot(store.bank, rec.ft_slot),
                               _slot(eng.opt_state.m, rec.ft_slot)))
    mgr.cache = _copy_block(mgr.cache, jnp.int32(0), jnp.int32(0))
    jax.block_until_ready(mgr.cache)
    return n


# -- one run ------------------------------------------------------------------

def served_of(eng, reqs: Dict[int, Dict], t_load: float):
    from repro.serving.request import State
    out = []
    for r in eng.all_requests:
        out.append(stats.Served(arrival=reqs[r.rid]["arrival"] + t_load,
                                token_times=list(r.token_times),
                                failed=r.state is State.FAILED))
    return out


def sample(eng, reqs: Dict[int, Dict], k: int, seed: int) -> List[Dict]:
    """Finished requests to compare, drawn from the seed, the longest among
    them."""
    from repro.serving.request import State
    done = sorted((r for r in eng.finished if r.state is State.DONE
                   and len(r.output) == reqs[r.rid]["max_new"]),
                  key=lambda r: r.rid)
    if not done:
        return []
    longest = max(done, key=lambda r: r.prompt_len + len(r.output))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 21])
    pick = [longest] + [rest[i] for i in
                        rng.choice(len(rest), min(k - 1, len(rest)),
                                   replace=False)]
    return [dict(rid=r.rid, prompt=reqs[r.rid]["prompt"],
                 output=np.asarray(r.output, np.int32),
                 adapter=reqs[r.rid]["adapter"]) for r in pick]


def training_numbers(got: Dict, want: Dict) -> Dict[str, float]:
    """The fine-tune's numbers for ``got`` (first optimizer steps, as
    ``Recorder.training`` or ``Reference.train`` give them) against the
    reference's ``want``: the widest per-row loss gap, and by the worst
    moving leaf the gap between the first gradient's norms, the norm of
    their difference, and the gap between the norms of the change."""
    keep = ref_mod.moving_leaves(want["grad"])
    return {
        "ft_loss_gap": float(max(np.abs(g - w).max() for g, w in
                                 zip(got["losses"], want["losses"]))),
        "ft_grad_gap": ref_mod.leaf_gap(got["grad"], want["grad"], keep),
        "ft_grad_diff": ref_mod.leaf_gap(got["grad"], want["grad"], keep,
                                         diff=True),
        "ft_change_gap": ref_mod.leaf_gap(got["change"], want["change"],
                                          keep),
        "ft_leaves": float(len(keep)),
    }


def compare(cell: Cell, make_params, make_adapters, seed: int,
            served: List[Dict], training: Optional[Dict],
            trained_index: int, study: bool = False) -> Dict[str, float]:
    """Draw the weights again from the seed and read, with the reference,
    the numbers the cell compares.  With ``study``, also read the control in
    the program's place (``<number>.control``: the gap of the token the
    float8 reference puts first, and the float8 reference's fine-tune), and
    faults planted in the reference's place (``<number>.half_rows``: each
    step's gradient from half of its rows, the mean over them;
    ``<number>.state_unchanged``: no gradient and no change)."""
    params = make_params(weights.seed_key(seed, 0))
    adapters = make_adapters(weights.seed_key(seed, 1))
    s_pad = cell.setup["engine"]["s_max"]
    p_pad = cell.traffic["output_tokens"]["max"]
    ref = cell.arch.Reference(cell.config)
    ctl = cell.arch.Reference(cell.config, fp8=True) if study else None
    out = {}
    if served:
        lg = ref.served_logits(params, adapters, served, s_pad, p_pad)
        out["token_gap"] = float(ref_mod.token_gaps(
            lg, [s["output"] for s in served]).max())
        if study:
            first = [c.argmax(-1) for c in
                     ctl.served_logits(params, adapters, served, s_pad,
                                       p_pad)]
            out["token_gap.control"] = float(
                ref_mod.token_gaps(lg, first).max())
    if training is not None:
        t = cell.traffic["trainer"]
        w, pad = 1.0 / t["accum_steps"], t["tokens"]["max"]

        def train(by, rows_of):
            return by.train(params, adapters, trained_index,
                            [rows_of(rows) for rows in training["steps"]],
                            t["optimizer"], pad)
        want = train(ref, lambda rows: [(x, w) for x in rows])
        out.update(training_numbers(training, want))
        if study:
            planted = {
                "control": train(ctl, lambda rows: [(x, w) for x in rows]),
                "half_rows": train(ref, lambda rows: [
                    (x, 2 * w if i < len(rows) // 2 else 0.0)
                    for i, x in enumerate(rows)]),
                "state_unchanged": dict(
                    want, grad={k: 0 * v for k, v in want["grad"].items()},
                    change={k: 0 * v for k, v in want["change"].items()})}
            for tag, got in planted.items():
                out.update({f"{k}.{tag}": v for k, v in
                            training_numbers(got, want).items()})
    del params, adapters
    return out


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: Cell
    chips: int
    peaks: Dict
    setup_s: float
    w0: float
    w1: float
    ticks: List[Tick]
    served: List[stats.Served]
    counters: Dict[str, int]
    trace: Optional[Dict] = None

    @property
    def window_s(self) -> float:
        return self.w1 - self.w0


def device_info(devs) -> Dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


COUNTERS = ("prefill_tokens", "reused_prefix_tokens", "decode_tokens",
            "finetune_tokens", "steps")


@dataclasses.dataclass
class Built:
    """The model and its adapters, made from the seed."""
    cfg: object
    lcfg: object
    names: List[str]
    model: object
    make_params: Callable
    make_adapters: Callable
    seconds: float

    @property
    def trains(self) -> bool:
        return TRAINED in self.names


def build(cell: Cell, seed: int) -> Built:
    """Weights and adapters drawn on the device by one jitted program each,
    the adapters loaded into the program's store."""
    from repro.core.virtualization import AdapterStore, MixedLoraModel
    cfg = cell.arch.model_config(cell.config)
    names = [f"a{i}" for i in range(cell.traffic["adapters"])] \
        + ([TRAINED] if "trainer" in cell.traffic else [])
    lcfg = lora_config(cell, len(names))
    make_params, make_adapters = makers(cell, cfg, lcfg, len(names))
    t0 = time.perf_counter()
    params = jax.block_until_ready(make_params(weights.seed_key(seed, 0)))
    stacked = jax.block_until_ready(make_adapters(weights.seed_key(seed, 1)))
    store = AdapterStore(cfg, lcfg, jax.random.PRNGKey(0))
    for i, name in enumerate(names):
        store.load(name, jax.tree_util.tree_map(lambda x: x[i], stacked))
    del stacked
    jax.block_until_ready(store.bank)
    return Built(cfg, lcfg, names, MixedLoraModel(cfg, params, store),
                 make_params, make_adapters, time.perf_counter() - t0)


@dataclasses.dataclass
class Window:
    """What a served window leaves behind, on the host."""
    run: Run
    device: Dict
    picked: List[Dict]
    training: Optional[Dict]
    compiles: int
    queue_growth: float


def serve(cell: Cell, built: Built, seed: int, seconds: float, rate: float,
          trace: bool, t_start: float, compiles, log=print) -> Window:
    """Engine, warm-up, pre-roll and the measured window; the engine is
    gone when this returns."""
    from repro.serving.engine import UnifiedEngine
    from repro.serving.request import Request
    from repro.training.trainer import MixedLoraTrainer, TrainerConfig
    mix, cfg, store = cell.traffic, built.cfg, built.model.store
    t0 = time.perf_counter()
    eng = UnifiedEngine(built.model, engine_config(cell))
    ft_slot = store.slot_of(TRAINED) if built.trains else -2
    rec = Recorder(eng, ft_slot, TRAIN_STEPS if built.trains else 0)
    t_engine = time.perf_counter() - t0
    t0 = time.perf_counter()
    n_shapes = warm(eng, cell, rec)
    t_warm = time.perf_counter() - t0
    if built.trains:
        t = mix["trainer"]
        rows = traffic_mod.make_train_rows(mix, cfg.vocab, seed)
        eng.add_trainer(MixedLoraTrainer(
            TRAINED, ft_slot, [(r, r.copy()) for r in rows], [],
            TrainerConfig(rows_per_micro=t["rows_per_micro"],
                          accum_steps=t["accum_steps"],
                          epochs=10 ** 9, eval_each_epoch=False)))
    preroll = float(mix["preroll_s"])
    reqs = {r["rid"]: r for r in traffic_mod.make_requests(
        mix, cfg.vocab, rate, preroll + seconds + 2.0, seed)}
    n_c, c_s = compiles()
    setup_s = time.perf_counter() - t_start
    log(f"setup: {setup_s:.2f} s (weights and adapters "
        f"{built.seconds:.2f} s, engine {t_engine:.2f} s, {n_shapes} step "
        f"shapes warmed in "
        f"{t_warm:.2f} s, {n_c} compiles taking {c_s:.2f} s)")

    # pre-roll, then the window, on one schedule of arrivals
    t_load = eng.clock.now()
    for r in sorted(reqs.values(), key=lambda r: r["arrival"]):
        eng.submit(Request(rid=r["rid"], prompt=r["prompt"].copy(),
                           adapter=built.names[r["adapter"]],
                           max_new_tokens=r["max_new"],
                           arrival=t_load + r["arrival"]))
    drive(eng, rec, t_load + preroll)
    before = {k: getattr(eng.metrics, k) for k in COUNTERS}
    n_c0, _ = compiles()
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    rec.recording = True
    w0 = eng.clock.now()
    drive(eng, rec, w0 + seconds)
    w1 = rec.ticks[-1].t1 if rec.ticks else eng.clock.now()
    rec.recording = False
    if trace:
        jax.profiler.stop_trace()
    n_c1, _ = compiles()
    log(f"window: {w1 - w0:.3f} s, {len(rec.ticks)} ticks, "
        f"{n_c1 - n_c0} compiles")
    rec.sizes(cell, built.lcfg.r)
    devs = jax.devices()[:cell.chips]
    run = Run(cell=cell, chips=cell.chips,
              peaks=(stats.peaks(devs[0].device_kind)
                     if devs[0].platform == "tpu" else {}),
              setup_s=setup_s, w0=w0, w1=w1, ticks=rec.ticks,
              served=served_of(eng, reqs, t_load),
              counters={k: getattr(eng.metrics, k) - before[k]
                        for k in COUNTERS})
    win = Window(run=run, device=device_info(devs),
                 picked=sample(eng, reqs,
                               int(cell.setup["sample_requests"]), seed),
                 training=(rec.training(eng.ecfg.opt.b1,
                                        built.lcfg.targets)
                           if built.trains else None),
                 compiles=n_c1 - n_c0,
                 queue_growth=stats.queue_growth(
                     [(x.t1, x.waiting) for x in rec.ticks]))
    del eng, rec
    gc.collect()
    return win


def read_metrics(run: Run, kind: str) -> Dict:
    out = {}
    for m in run.cell.metrics:
        if m["kind"] != kind:
            continue
        fn = reader(m["name"])
        v = fn(run, m["name"]) if fn is not None else None
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, log=print) -> Dict:
    """One run; returns the result line's object.  ``t_start`` is the
    process's start on ``time.perf_counter``."""
    compiles = compile_seconds()
    built = build(cell, seed)
    win = serve(cell, built, seed, seconds, float(cell.setup["rate_rps"]),
                trace, t_start, compiles, log)
    make_params, make_adapters = built.make_params, built.make_adapters
    trained = built.names.index(TRAINED) if built.trains else -1
    del built
    gc.collect()

    run, device = win.run, win.device
    if trace:
        from perfbench import trace as trace_mod
        run.trace = trace_mod.reduce_dir(TRACE_DIR)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    metrics = read_metrics(run, "per_layer" if trace else "end_to_end")

    t0 = time.perf_counter()
    got = compare(cell, make_params, make_adapters, seed, win.picked,
                  win.training, trained)
    rows = sum(len(s) for s in win.training["steps"]) if win.training else 0
    log(f"reference: {len(win.picked)} requests, "
        f"{sum(len(p['output']) for p in win.picked)} served tokens, "
        f"{rows} fine-tune rows in {TRAIN_STEPS if rows else 0} optimizer "
        f"steps ({got.get('ft_leaves', 0):.0f} leaves compared), "
        f"{time.perf_counter() - t0:.2f} s")
    checks = {k: {"value": got.get(k), "limit": lim}
              for k, lim in cell.setup["limits"].items()}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    due = stats.due(run.served, run.w0, run.w1)
    out = {"correct": bool(correct), "attempted": len(due),
           "failed": sum(r.failed for r in due), "metrics": metrics,
           "device": device}
    if trace:
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["checks"] = checks
    return out
