"""UnifiedEngine — the Loquetier runtime: one loop, four request kinds.

Every tick assembles ONE unified batch (fine-tune + eval + prefill + decode),
executes ONE jit'd step (with a shared backward pass when fine-tune rows are
present), then scatters results back: sampled tokens to inference requests,
per-row losses to their trainers, accumulated gradients to the masked
optimizer on each trainer's accumulation boundary.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core import flow
from repro.core.unified import make_apply_step, make_forward_step, make_grad_step
from repro.core.virtualization import MixedLoraModel
from repro.models.stream import UnifiedBatch
from repro.serving.clock import CostModel, VirtualClock, WallClock
from repro.serving.kvcache import (CacheManager, OutOfBlocksError,
                                   PagedCacheManager, request_chain_keys,
                                   swap_beats_recompute)
from repro.serving.request import Request, State
from repro.serving.scheduler import Scheduler, SchedulerConfig
from repro.serving.slo import Metrics, SLOConfig, spread_token_times
from repro.spec import AdaptiveK, Drafter, SpecConfig, accept_greedy, \
    make_drafter
from repro.training.optimizer import (AdamWConfig, adamw_init, tree_add,
                                      tree_mask_slots, tree_zeros_like)
from repro.training.trainer import MixedLoraTrainer


@dataclasses.dataclass
class EngineConfig:
    capacity: int = 8                 # max concurrent decode requests
    pf_capacity: int = 4              # prefill scratch rows
    s_max: int = 256                  # cache sequence capacity
    scheduler: SchedulerConfig = dataclasses.field(default_factory=SchedulerConfig)
    slo: SLOConfig = dataclasses.field(default_factory=SLOConfig)
    opt: AdamWConfig = dataclasses.field(default_factory=AdamWConfig)
    flow: flow.FlowConfig = dataclasses.field(default_factory=flow.FlowConfig)
    attn_chunk: int = 0
    virtual_time: bool = False        # deterministic trace replay
    paged: bool = True                # block-table KV layout (falls back to
    #                                   dense rows for sliding-window models)
    block_size: int = 32              # KV tokens per block (paged layout)
    n_blocks: int = 0                 # pool size; 0 = match dense capacity
    over_admit: float = 1.0           # reservation lending factor (>= 1):
    #                                   the admission gate charges only a
    #                                   1/over_admit slice of outstanding
    #                                   reservation debt and lends the rest
    #                                   to new admissions; growth failures
    #                                   are repaid by recompute preemption
    #                                   (1.0 = conservative gate, no lending)
    spec: Optional[SpecConfig] = None  # speculative decoding (paged,
    #                                   attention-only models; exact greedy)
    prefill_chunk: int = 0            # per-tick prefill-token budget: long
    #                                   prompts prefill as a sequence of
    #                                   bounded chunks that co-batch with
    #                                   decode/ft rows (0 = unchunked)
    hash_dedup: bool = True           # content-hash block dedup: every full
    #                                   KV block is indexed by a chained
    #                                   sha1(adapter, parent, tokens) key,
    #                                   admission adopts resident runs (no
    #                                   recompute, no caller-side id), and
    #                                   the scheduler prefers admitting
    #                                   requests whose prefixes are resident
    #                                   (False = escape hatch: no reuse)
    adapter_paging: bool = False      # unified KV + adapter paging: adapter
    #                                   weights page through the SAME block
    #                                   pool as the KV cache (S-LoRA
    #                                   unified memory — HBM flows between
    #                                   cache capacity and adapter
    #                                   residency instead of being
    #                                   statically partitioned), the
    #                                   scheduler becomes adapter-
    #                                   residency-aware, and swap-ins are
    #                                   charged to the virtual clock.
    #                                   Default OFF: the static bank
    #                                   partition is the baseline
    cost: Optional[CostModel] = None  # virtual-clock cost model override
    kv_host_blocks: int = 0           # tiered KV memory: host-side block
    #                                   pool budget, in device blocks' worth
    #                                   of host RAM.  > 0 enables swap-to-
    #                                   host preemption (victims' blocks
    #                                   D2H, restored H2D at re-admission
    #                                   when the modeled transfer beats
    #                                   suffix recompute) and demotion of
    #                                   shed index blocks to the host tier.
    #                                   0 = recompute-only preemption
    #                                   (byte-identical baseline)
    kv_host_quant: bool = False       # int8-quantize host-tier residency
    #                                   (~2x host capacity at equal budget).
    #                                   EXACTNESS-EXEMPT: dequantized KV is
    #                                   not bit-identical, so outputs may
    #                                   differ from the recompute path —
    #                                   hence an explicit opt-in


def _step_attrs(kind: str):
    """The dispatch span's attributes: the step's kind and each segment's
    bucket rows."""
    def attrs(base, bank, scale, batch, cache):
        return dict(kind=kind, **{
            f"{seg}_rows": getattr(batch, seg).tokens.shape[0]
            for seg in ("ft", "pf", "dec")
            if getattr(batch, seg) is not None})
    return attrs


@dataclasses.dataclass
class _Plan:
    """One tick's work as planned on the host: what ``_step`` runs and
    ``_scatter`` reads back."""
    decision: object
    ft_rows: List[flow.FTRow]
    pf_reqs: List[flow.PFReq]
    chunks: List[Tuple[Request, int, bool]]  # (request, tokens, final?)
    Sd: int                                  # decode chunk width
    drafts: Dict[int, np.ndarray]            # slot -> speculative drafts
    planned: frozenset                       # slots with a decode row
    batch: UnifiedBatch
    cache: object


class UnifiedEngine:
    def __init__(self, model: MixedLoraModel, ecfg: Optional[EngineConfig] = None):
        self.model = model
        self.ecfg = ecfg or EngineConfig()
        self.cfg = model.cfg
        e = self.ecfg
        self.paged = e.paged and self.cfg.sliding_window == 0
        self._cost = e.cost or CostModel()
        if self.paged:
            self.cachemgr = PagedCacheManager(
                self.cfg, e.capacity, e.pf_capacity, e.s_max,
                block_size=e.block_size, n_blocks=e.n_blocks,
                over_admit=e.over_admit, hash_dedup=e.hash_dedup,
                host_blocks=e.kv_host_blocks, host_quant=e.kv_host_quant,
                cost=self._cost)
        else:
            self.cachemgr = CacheManager(self.cfg, e.capacity, e.pf_capacity,
                                         e.s_max)
        # unified adapter paging: adapter weights share the KV block pool
        self.adapter_paging = self.paged and e.adapter_paging
        if self.adapter_paging:
            model.store.attach_pager(self.cachemgr)
        # swap counters are store-lifetime; baseline them so pre-serving
        # loads are not billed to (or reported for) this engine
        st = model.store
        self._swaps_base = (st.swap_ins, st.swap_in_bytes, st.resident_hits)
        self._swaps_seen = self._swaps_base[:2]
        self.sched = Scheduler(e.scheduler, e.capacity)
        self.clock = VirtualClock(e.cost) if e.virtual_time else WallClock()
        self.metrics = Metrics()
        # suffix-only prefill reads shared-prefix K/V through the block
        # tables instead of recomputing it; chunked prefill additionally
        # needs per-chunk resumability.  Both require a positional paged
        # cache — mamba SSM state cannot resume mid-prompt from blocks.
        self.suffix_prefill = self.paged and "mamba" not in self.cfg.pattern
        self.chunk_budget = (e.prefill_chunk
                             if e.prefill_chunk > 0 and self.suffix_prefill
                             else 0)
        self.prefilling: Dict[int, Request] = {}  # slot -> partial prefill
        self.hash_dedup = self.paged and e.hash_dedup
        # tiered KV memory: swap-outs are only worth taking when restore
        # can actually skip the restored span — which is the suffix-prefill
        # cached_len path.  Models that must recompute the full prompt
        # (hybrid/mamba) keep recompute-only preemption; demote/rehydrate
        # of index blocks rides the normal adoption path and stays on.
        self.kv_tiering = (self.paged and e.kv_host_blocks > 0
                           and self.suffix_prefill)
        self._kv_seen = (0, 0)                 # (d2h, h2d) bytes charged
        # every swap-vs-recompute decision, in order — bench_tiers replays
        # the rule analytically against this log and gates on an exact
        # match (the "decision hit rate")
        self.swap_decisions: List[dict] = []

        # the dispatch span opens inside the stored callables, so a caller
        # that wraps them (and blocks) still sees the enqueue alone
        self.forward_step = spans.wrap(
            "serve.dispatch",
            make_forward_step(self.cfg, attn_chunk=e.attn_chunk),
            _step_attrs("fwd"))
        self.grad_step = spans.wrap(
            "serve.dispatch",
            make_grad_step(self.cfg, attn_chunk=e.attn_chunk),
            _step_attrs("grad"))
        self.apply_step = make_apply_step(e.opt)
        self.opt_state = adamw_init(model.store.bank,
                                    model.store.lcfg.n_slots)
        self.grad_accum = tree_zeros_like(model.store.bank)

        self.future: List[Request] = []       # arrival-sorted
        self.waiting: List[Request] = []
        self.active: Dict[int, Request] = {}  # decode slot -> request
        self.finished: List[Request] = []
        self.trainers: Dict[str, MixedLoraTrainer] = {}
        self._last_tokens = np.zeros((e.capacity,), np.int64)
        # speculative decoding: needs rollback-able K/V (paged blocks) and a
        # positional cache — mamba SSM state cannot un-consume drafts
        self.spec = e.spec if (e.spec is not None and e.spec.enabled
                               and self.paged
                               and "mamba" not in self.cfg.pattern) else None
        self._spec: Dict[int, Tuple[Optional[Drafter], AdaptiveK]] = {}

    @property
    def spec_headroom(self) -> int:
        """Transient +k draft tokens each resident request may hold
        mid-verify — charged to its block budget at admission."""
        return self.spec.k_max if self.spec else 0

    def _headroom_for(self, r: Request) -> int:
        """Per-request draft headroom: when the +k charge would push the
        request past the whole pool (it fits its plain projection but not
        the inflated one), admit it with NO reserved draft room instead of
        stranding it un-admittable — its drafts then ride the best-effort
        overshoot path in ``grow`` and are trimmed when the pool is dry."""
        h = self.spec_headroom
        if h and self.cachemgr.projected_blocks(
                r.prompt_len, r.remaining_new + h) \
                > self.cachemgr.total_blocks:
            return 0
        return h

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        if req.arrival > self.clock.now():
            self.future.append(req)
            self.future.sort(key=lambda r: r.arrival)
        else:
            self.waiting.append(req)

    def add_trainer(self, tr: MixedLoraTrainer):
        self.trainers[tr.name] = tr
        # training adapters must never be evicted: their slot identity is
        # baked into the trainer mask and optimizer state (pinning a name
        # before its load is fine — the pin is checked against residents)
        # reprolint: ownership-transfer — pin lives for the trainer's life
        self.model.store.pin(tr.name)

    def trainers_pending(self) -> bool:
        return any(t.pending() for t in self.trainers.values())

    def _keys_of(self, r: Request) -> Optional[List[str]]:
        """The request's block-key chain for the dedup index, or None when
        the request must not share — modality embeddings make deeper-layer
        K/V depend on aux_embed, which the (adapter, tokens) content
        identity cannot capture.  The memoization itself lives in
        ``kvcache.request_chain_keys`` so the fleet router and engine
        admission hash each prompt ONCE between them, not once per layer
        that asks."""
        if not self.hash_dedup or r.aux_embed is not None:
            return None
        return request_chain_keys(r, self.cachemgr.block_size)

    def _resident_tokens(self, r: Request) -> int:
        """Prompt tokens the dedup index would serve without recompute."""
        keys = self._keys_of(r)
        if keys is None:
            return 0
        return self.cachemgr.probe(r.prompt, r.adapter, keys=keys)

    def _pull_arrivals(self):
        now = self.clock.now()
        while self.future and self.future[0].arrival <= now:
            self.waiting.append(self.future.pop(0))

    # ------------------------------------------------------------------
    def tick(self) -> bool:
        """One scheduling + execution round; returns False when idle."""
        spans.follow_profiler()
        with spans.span("serve.tick") as sp:
            with spans.span("serve.plan"):
                p = self._plan()
            if p is None:
                # idle: jump to next arrival if replaying a trace
                if self.future:
                    self.clock.advance_to(self.future[0].arrival)
                    return True
                return False
            if sp:
                sp.set(kind="grad" if p.ft_rows else "fwd",
                       **self._kv_blocks())
            out, grads = self._step(p)
            with spans.span("serve.scatter"):
                self._scatter(p, out, grads)
        return True

    def _plan(self) -> Optional[_Plan]:
        """The tick's host side up to its assembled batch: arrivals, the
        unservable sweep, the scheduler's decision, trainer rows,
        admission, decode growth and preemption.  None when idle."""
        self._pull_arrivals()
        e = self.ecfg
        # prefill rows this tick: continuing partial-prefill chunks first
        # (they already hold slots), then fresh admissions.  ``chunks``
        # parallels ``pf_reqs``: (request, computed tokens, final chunk?).
        pf_reqs: List[flow.PFReq] = []
        chunks: List[Tuple[Request, int, bool]] = []
        budget_left = self.chunk_budget if self.chunk_budget else None
        if self.paged:
            for slot, r in list(self.prefilling.items()):
                if len(pf_reqs) >= e.pf_capacity:
                    break
                if budget_left is not None and budget_left <= 0:
                    break
                rem = r.prompt_len - r.prefilled
                take = rem if budget_left is None else min(rem, budget_left)
                if budget_left is not None:
                    budget_left -= take
                pf_reqs.append(flow.PFReq(
                    tokens=r.prompt[r.prefilled:r.prefilled + take],
                    rid=r.rid,
                    slot=(self.model.store.slot_of(r.adapter)
                          if r.adapter else -1),
                    aux_embed=r.aux_embed,
                    block_table=self.cachemgr.table_of(slot),
                    cached_len=r.prefilled))
                chunks.append((r, take, r.prefilled + take >= r.prompt_len))
        if self.paged:
            # a request is unservable only when its FRESH block need —
            # projected blocks minus index-resident blocks it would adopt —
            # can never fit the pool.  Gating on raw projected blocks
            # wrongly FAILED long prompts that fit suffix-only over a
            # shared prefix.  Only ref >= 2 shared blocks discount
            # (fresh_need's held_elsewhere): an index-only (ref == 1) block
            # has no concurrent payer, so this request's own footprint
            # would include it — adopting it does not shrink the total the
            # pool must hold for this request, and if that total exceeds
            # the pool the request truly can never fit (admitting it would
            # just wedge in the waiting queue forever instead).
            for r in list(self.waiting):
                # cheap precheck: fresh_need <= projected_blocks always, so
                # the prefix-token compare can only change the verdict when
                # the raw projection already overflows the pool — a deep
                # backlog must not pay an O(prefix) sweep per tick
                if self.cachemgr.projected_blocks(
                        r.prompt_len, r.remaining_new) \
                        <= self.cachemgr.total_blocks:
                    continue
                need = self.cachemgr.fresh_need(
                    r.prompt_len, r.remaining_new, r.prompt, r.adapter,
                    keys=self._keys_of(r),
                    shareable=r.aux_embed is None)
                if need > self.cachemgr.total_blocks:
                    r.state = State.FAILED
                    r.t_finish = self.clock.now()
                    self._drop_retain(r)
                    self._drop_swap(r)
                    self.waiting.remove(r)
                    self.finished.append(r)
        with spans.span("serve.decide"):
            decision = self._decide(pf_reqs, budget_left)

        # fine-tuning rows (round-robin over trainers)
        ft_rows: List[flow.FTRow] = []
        budget = decision.ft_rows
        for tr in self.trainers.values():
            if budget <= 0:
                break
            got = tr.next_rows(budget)
            ft_rows.extend(got)
            budget -= len(got)

        # prefill admissions.  Adapters are resolved ONCE per tick per name
        # (memoized below): the first same-adapter admit pays the swap-in,
        # every co-scheduled follower rides it free — the amortization the
        # scheduler's greedy affinity pass set up.  Each resolved adapter is
        # held (retain) for the rest of the loop so a later resolve cannot
        # evict it out from under an earlier admit; the temporary holds are
        # dropped in the ``finally`` whether or not admission succeeded.
        resolved: Dict[str, int] = {}
        unknown: set = set()
        deferred: set = set()

        # reprolint: ownership-transfer — holds land in ``resolved``; the
        # finally around _admit_loop releases every one exactly once
        def _resolve(name: str):
            if name in resolved or name in unknown or name in deferred:
                return
            try:
                resolved[name] = self.model.store.acquire(name)
                self.model.store.retain(name)
            except KeyError:
                unknown.add(name)
            except RuntimeError:
                deferred.add(name)     # bank/pool saturated this tick

        with spans.span("serve.admit") as sp:
            try:
                admitted = self._admit_loop(decision, pf_reqs, chunks,
                                            budget_left, resolved, unknown,
                                            deferred, _resolve)
            finally:
                for name in resolved:
                    self.model.store.release(name)
            if sp:
                first = [r for r, new in admitted if new]
                sp.set(rid=[r.rid for r, _ in admitted],
                       admissions=len(first),
                       queue_wait_s=sum(r.t_admit - r.arrival
                                        for r in first))

        # decode / verify bucket (static: full table when any request is
        # active; chunk width 1 + k_max whenever speculation is on, so the
        # bucket shape compiles once)
        use_dec = bool(self.active)
        Sd = 1 + (self.spec.k_max if (self.spec and use_dec) else 0)
        drafts: Dict[int, np.ndarray] = {}
        dec_lens = None
        plans: List[Tuple[int, Request, int, np.ndarray]] = []
        if use_dec:
            # phase 1 — drafts + block growth, with recompute preemption as
            # the growth-failure backstop.  Slots carrying a prefill row
            # this tick are pinned: their PFReq already snapshot a block
            # table, so freeing them would hand the model dangling blocks.
            pinned = frozenset(c[0].dec_slot for c in chunks)
            for slot, r in list(self.active.items()):
                if slot not in self.active:
                    continue              # preempted as an earlier victim
                L = int(self.cachemgr.lens[slot])
                draft = np.zeros((0,), np.int64)
                if Sd > 1:
                    drafter, ctl = self._spec[slot]
                    # clamp drafts to what the request can still emit and
                    # to the context limit (writes land at L .. L + k)
                    k = min(ctl.k, r.max_new_tokens - len(r.output) - 1,
                            e.s_max - 1 - L)
                    if k > 0 and drafter is not None:
                        # prompt already embeds output[:rolled] after a
                        # preemption — append only the unrolled tail, or
                        # the history duplicates tokens and the suffix
                        # drafter's position index drifts
                        draft = np.asarray(drafter.draft(
                            np.concatenate([np.asarray(r.prompt, np.int64),
                                            np.asarray(r.output[r.rolled:],
                                                       np.int64)]),
                            k), np.int64)
                if self.paged:
                    # grow the block table over the chunk's positions and
                    # copy-on-write any shared block in the write range; a
                    # dry pool trims the transient draft tail, and — under
                    # over-admission — preempts when even the committed
                    # token at L no longer fits
                    writable = self._grow_or_preempt(slot, r, L,
                                                     1 + len(draft), pinned)
                    if slot not in self.active:
                        continue          # became its own victim
                    draft = draft[:max(writable - 1, 0)]
                plans.append((slot, r, L, draft))
            # a slot planned early may have been preempted as a victim of a
            # later grower — only survivors get a decode row
            plans = [p for p in plans if p[0] in self.active]
            use_dec = bool(plans)
        planned = frozenset(p[0] for p in plans)
        if use_dec:
            # phase 2 — assemble the bucket from surviving rows
            dec_tokens = (np.zeros((e.capacity, Sd), np.int64) if Sd > 1
                          else np.zeros((e.capacity,), np.int64))
            dec_pos = np.zeros((e.capacity,), np.int64)
            dec_slots = np.full((e.capacity,), -1, np.int64)
            if Sd > 1:
                dec_lens = np.zeros((e.capacity,), np.int64)
            for slot, r, L, draft in plans:
                if Sd > 1:
                    dec_tokens[slot, 0] = self._last_tokens[slot]
                    if len(draft):
                        dec_tokens[slot, 1:1 + len(draft)] = draft
                    dec_lens[slot] = 1 + len(draft)
                    drafts[slot] = draft
                else:
                    dec_tokens[slot] = self._last_tokens[slot]
                dec_pos[slot] = L
                dec_slots[slot] = (self.model.store.slot_of(r.adapter)
                                   if r.adapter else -1)
            dec_tables = (self.cachemgr.dec_tables(self.active)
                          if self.paged else None)
        else:
            dec_tokens = dec_pos = dec_slots = np.zeros((0,), np.int64)
            dec_tables = None

        if not ft_rows and not pf_reqs and not use_dec:
            return None

        with spans.span("serve.assemble") as sp:
            batch = flow.assemble(ft_rows, pf_reqs, dec_tokens, dec_pos,
                                  dec_slots, e.flow, dec_tables=dec_tables,
                                  dec_lens=dec_lens)
            if sp:
                # real tokens against the bucket positions they were
                # padded to
                sp.set(step_tokens=(sum(len(r.tokens) for r in ft_rows)
                                    + sum(len(q.tokens) for q in pf_reqs)
                                    + sum(1 + len(p[3]) for p in plans)),
                       step_positions=sum(
                           s.tokens.size for s in (batch.ft, batch.pf,
                                                   batch.dec)
                           if s is not None))
        # chunked-prefill SLO invariant, checked on the ASSEMBLED batch: a
        # step that runs prefill while requests are mid-decode must carry
        # their decode bucket — any future path that builds a prefill step
        # without one trips this (gated to 0 in bench_prefix / CI)
        if pf_reqs and self.active and batch.dec is None:
            self.metrics.starved_ticks += 1
        cache = self.cachemgr.step_cache() if (pf_reqs or use_dec) else None
        return _Plan(decision, ft_rows, pf_reqs, chunks, Sd, drafts, planned,
                     batch, cache)

    def _kv_blocks(self) -> Dict[str, int]:
        """The blocks the live requests' tables hold (shared ones once),
        and those plus the reservations they may still grow into."""
        if not self.paged:
            return {}
        used = len(set().union(*self.cachemgr.tables.values()))
        return dict(kv_blocks_used=used,
                    kv_blocks_reserved=used + self.cachemgr.reserved_debt)

    def _decide(self, pf_reqs: List[flow.PFReq], budget_left: Optional[int]):
        e = self.ecfg
        if not self.paged:
            return self.sched.decide(self.waiting, len(self.active),
                                     self.cachemgr.n_free, e.pf_capacity,
                                     self.trainers_pending())
        suffix_fn = None
        if self.suffix_prefill:
            suffix_fn = lambda r: r.prompt_len - self._resident_tokens(r)
        return self.sched.decide(
            self.waiting, len(self.active) + len(self.prefilling),
            self.cachemgr.n_free,
            e.pf_capacity, self.trainers_pending(),
            # index-held blocks are sheddable inside try_admit, so the
            # gate must count them as available
            free_blocks=(self.cachemgr.free_blocks
                         + self.cachemgr.reclaimable_blocks),
            total_blocks=self.cachemgr.total_blocks,
            block_size=self.cachemgr.block_size, s_max=e.s_max,
            need_fn=lambda r: self.cachemgr.fresh_need(
                r.prompt_len, r.remaining_new, r.prompt, r.adapter,
                headroom=self._headroom_for(r), keys=self._keys_of(r),
                shareable=r.aux_embed is None),
            spec_headroom=self.spec_headroom,
            pf_rows_used=len(pf_reqs), pf_token_budget=budget_left,
            suffix_fn=suffix_fn, chunked=bool(self.chunk_budget),
            # actually-lent debt fraction: the preemption precursor
            # that makes fine-tuning yield before inference is evicted
            lent_frac=(self.cachemgr.lent_blocks
                       / max(self.cachemgr.reserved_debt, 1)),
            # prefix-aware admission: prefer requests whose prompt heads
            # are index-resident (their prefill is mostly free), bounded
            # by the fairness ramp so cold requests cannot starve
            probe_fn=(self._resident_tokens if self.hash_dedup
                      else None),
            # adapter-residency-aware admission: only under unified
            # paging — the static-bank baseline keeps the pre-paging
            # ordering byte-for-byte
            adapter_fn=((lambda r: self.model.store.is_resident(
                r.adapter)) if self.adapter_paging else None),
            now=self.clock.now())

    def _step(self, p: _Plan):
        """Run the planned batch's step and wait for it: (out, grads)."""
        store = self.model.store
        if p.ft_rows:
            res = self.grad_step(self.model.base, store.bank, store.scale,
                                 p.batch, p.cache)
            out, grads = res.out, res.grads
        else:
            out = self.forward_step(self.model.base, store.bank, store.scale,
                                    p.batch, p.cache)
            grads = None
        # the ONE deliberate step barrier: the clock charges a finished
        # step, and the scatter below needs its logits anyway
        with spans.span("serve.wait"):
            jax.block_until_ready(  # reprolint: sync-point
                out.dec_logits if out.dec_logits is not None
                else (out.pf_logits if out.pf_logits is not None
                      else out.ft_loss_sum))
        return out, grads

    def _scatter(self, p: _Plan, out, grads):
        """Everything after the step's barrier: the clock's charge, tokens
        to requests, losses to trainers, and the metrics."""
        e = self.ecfg
        store = self.model.store
        chunks, ft_rows, drafts = p.chunks, p.ft_rows, p.drafts
        use_dec = bool(p.planned)

        # ---- time accounting (suffix tokens only: skipped prefix spans
        # cost nothing, which is the whole point of the reuse) ----
        pf_tok = int(sum(take for _, take, _ in chunks))
        ft_tok = int(sum(len(r.tokens) for r in ft_rows))
        dec_extra = int(sum(len(d) for d in drafts.values()))
        if isinstance(self.clock, VirtualClock):
            # adapter swap-ins since the last charge (paged pool admits AND
            # static-bank voided reloads both count — equal H2D price)
            swaps = store.swap_ins - self._swaps_seen[0]
            swap_bytes = store.swap_in_bytes - self._swaps_seen[1]
            self._swaps_seen = (store.swap_ins, store.swap_in_bytes)
            # KV host-tier traffic since the last charge (same cumulative-
            # counter delta pattern as adapter swaps; bytes moved on a tick
            # that returned early are picked up by the next charging tick)
            kvd = kvh = 0
            if self.paged:
                kvd = self.cachemgr.kv_d2h_bytes - self._kv_seen[0]
                kvh = self.cachemgr.kv_h2d_bytes - self._kv_seen[1]
                self._kv_seen = (self.cachemgr.kv_d2h_bytes,
                                 self.cachemgr.kv_h2d_bytes)
            cost = self.clock.step_cost(pf_tok, len(self.active), ft_tok,
                                        dec_extra_tokens=dec_extra,
                                        adapter_swaps=swaps,
                                        adapter_swap_bytes=swap_bytes,
                                        kv_d2h_bytes=kvd,
                                        kv_h2d_bytes=kvh)
            self.clock.charge(cost)
            self.metrics.busy_time += cost
        now = self.clock.now()

        # ---- scatter results back ----
        if out.cache is not None:
            self.cachemgr.update(out.cache)
        if p.pf_reqs:
            # scheduling reads the sampled token: a required sync boundary
            with spans.span("serve.readback", what="pf_logits"):
                pf_logits = np.asarray(out.pf_logits)  # reprolint: sync-point
            assignments, lengths = [], []
            finals: List[Request] = []
            for i, (r, take, final) in enumerate(chunks):
                r.prefilled += take
                if r.recount_pending:
                    # post-preemption recompute, charged per chunk actually
                    # computed (never the whole suffix up front — a second
                    # preemption mid-prefill would double-count the rest)
                    self.metrics.preempted_tokens_recomputed += take
                    if final:
                        r.recount_pending = False
                assignments.append((i, r.dec_slot))
                lengths.append(r.prefilled)
                if final:
                    tok = int(pf_logits[i].argmax())
                    r.output.append(tok)
                    if r.t_first_token is None:
                        # a preempted request keeps its original first-token
                        # time: the re-prefill is recompute, not a new TTFT
                        r.t_first_token = now
                    r.token_times.append(now)
                    r.state = State.DECODE
                    self._last_tokens[r.dec_slot] = tok
                    self.active[r.dec_slot] = r
                    self.prefilling.pop(r.dec_slot, None)
                    finals.append(r)
                else:
                    # partial prefill: K/V through ``prefilled`` is in the
                    # blocks; the next chunk attends to it via cached_len
                    self.prefilling[r.dec_slot] = r
            # the model wrote prefill rows at [Bd, Bd+Bp): tell the manager
            # where they start (state rows only under the paged layout — the
            # K/V itself went straight into the request's blocks)
            # commit is also the dedup publication point: every full prompt
            # block the chunk filled enters the hash index (no caller-side
            # registration — content is the identity)
            self.cachemgr.commit_prefill(assignments, lengths,
                                         src_base=e.capacity if use_dec
                                         else 0)
            self.metrics.prefill_tokens += pf_tok
            self.metrics.max_pf_tokens_step = max(
                self.metrics.max_pf_tokens_step, pf_tok)
            for r in finals:
                self._maybe_finish(r, now)
        if use_dec:
            # argmax/accept decisions drive the next tick's inputs
            with spans.span("serve.readback", what="dec_logits"):
                dec_logits = np.asarray(out.dec_logits)  # reprolint: sync-point
            for slot, r in list(self.active.items()):
                if r.state is not State.DECODE or slot not in p.planned:
                    continue    # just (re-)prefilled this tick: no dec row
                if p.Sd > 1:
                    self._scatter_verify(slot, r, dec_logits[slot],
                                         drafts.get(slot), now)
                else:
                    tok = int(dec_logits[slot].argmax())
                    r.output.append(tok)
                    r.token_times.append(now)
                    # position L holds the K/V of this step's INPUT token —
                    # committing it may fill (and publish) a block
                    self.cachemgr.commit_tokens(
                        slot, [int(self._last_tokens[slot])])
                    self._last_tokens[slot] = tok
                    self.metrics.decode_tokens += 1
                    self._maybe_finish(r, now)

        if ft_rows:
            # per-trainer loss bookkeeping happens on host once per step
            with spans.span("serve.readback", what="ft_loss"):
                losses = np.asarray(out.ft_loss_sum)  # reprolint: sync-point
                counts = np.asarray(out.ft_tok_count)  # reprolint: sync-point
            per_row = losses / np.maximum(counts, 1.0)
            self.grad_accum = tree_add(self.grad_accum, grads)
            by_trainer: Dict[str, List] = {}
            train_tok = eval_tok = 0.0
            for i, row in enumerate(ft_rows):
                by_trainer.setdefault(row.trainer, []).append(
                    (row, float(per_row[i]), float(counts[i])))
                if row.is_eval:
                    eval_tok += float(counts[i])
                else:
                    train_tok += float(counts[i])
            for name, items in by_trainer.items():
                tr = self.trainers[name]
                rows = [it[0] for it in items]
                ls = [it[1] for it in items]
                cs = [it[2] for it in items]
                if tr.record(rows, ls, cs):
                    self._apply_trainer(tr)
            self.metrics.finetune_tokens += int(train_tok)
            self.metrics.eval_tokens += int(eval_tok)

        self.metrics.steps += 1
        self.metrics.elapsed = self.clock.now()
        self.metrics.probe_admissions += p.decision.probe_admissions
        # adapter residency accounting (store-lifetime counters, baselined
        # at engine construction so pre-serving loads are not reported)
        self.metrics.adapter_swap_ins = store.swap_ins - self._swaps_base[0]
        self.metrics.adapter_swap_in_bytes = (store.swap_in_bytes
                                              - self._swaps_base[1])
        self.metrics.adapter_resident_hits = (store.resident_hits
                                              - self._swaps_base[2])
        self.metrics.adapter_peak_coresident = store.peak_coresident
        if self.paged:
            self.metrics.lent_blocks_peak = self.cachemgr.lent_blocks_peak
            self.metrics.hash_hits = self.cachemgr.hash_hits
            self.metrics.hash_blocks_resident = \
                self.cachemgr.hash_blocks_resident
            self.metrics.remote_fetch_blocks = self.cachemgr.remote_imports
            if self.cachemgr.host_pool is not None:
                m, hp = self.cachemgr, self.cachemgr.host_pool
                self.metrics.kv_swap_outs = m.kv_swap_outs
                self.metrics.kv_swap_out_bytes = m.kv_swap_out_bytes
                self.metrics.kv_restores = m.kv_restores
                self.metrics.kv_restore_bytes = m.kv_restore_bytes
                self.metrics.kv_demotions = m.kv_demotions
                self.metrics.kv_rehydrated_blocks = m.kv_rehydrations
                self.metrics.host_bytes_peak = hp.peak_used_bytes
            if self.adapter_paging:
                self.metrics.adapter_blocks_resident = \
                    self.cachemgr.adapter_blocks_resident

    # ------------------------------------------------------- admission body
    def _admit_loop(self, decision, pf_reqs: List[flow.PFReq],
                    chunks: List[Tuple[Request, int, bool]],
                    budget_left: Optional[int],
                    resolved: Dict[str, int], unknown: set, deferred: set,
                    resolve):
        """Admission body of ``tick``, split out so the per-tick adapter
        holds can wrap it in try/finally.  Appends to ``pf_reqs``/``chunks``
        in place; ``budget_left`` is the remaining chunked-prefill token
        budget this tick (None = unchunked).  Returns the admitted
        requests, each with whether this was its first admission."""
        e = self.ecfg
        admitted: List[Tuple[Request, bool]] = []
        for r in decision.admit:
            if len(pf_reqs) >= e.pf_capacity:
                break
            # resolve the adapter BEFORE reserving cache resources: acquire
            # can fail (unknown adapter, or bank/pool saturated) and must
            # not leak a reservation or abort the tick.  A saturated
            # adapter defers only ITS requests — co-admitted requests on
            # other (or no) adapters still run this tick
            if r.adapter:
                resolve(r.adapter)
                if r.adapter in unknown:
                    r.state = State.FAILED
                    r.t_finish = self.clock.now()
                    self._drop_retain(r)
                    self._drop_swap(r)
                    self.waiting.remove(r)
                    self.finished.append(r)
                    continue
                if r.adapter not in resolved:
                    continue       # saturated: stays waiting, retries later
                aslot = resolved[r.adapter]
            else:
                aslot = -1
            reused = 0
            if self.paged:
                adm = self.cachemgr.try_admit(r.prompt, r.remaining_new,
                                              r.adapter,
                                              headroom=self._headroom_for(r),
                                              shareable=r.aux_embed is None,
                                              keys=self._keys_of(r),
                                              priority=r.priority_class)
                slot = adm[0] if adm is not None else None
                reused = adm[1] if adm is not None else 0
            else:
                slot = self.cachemgr.alloc()
            if slot is None:
                break
            if r.swap_sid is not None:
                # re-admission of a swapped-out victim: the H2D restore
                # covers its rolled context minus one live token, so the
                # suffix prefill below recomputes exactly that token —
                # byte-identical to the recompute path, without the
                # recompute
                restored = self.cachemgr.restore_swap(slot, r.swap_sid)
                r.swap_sid = None
                if restored > reused:
                    self.metrics.kv_restored_tokens += restored - reused
                    reused = restored
            if r.adapter and not r.adapter_retained:
                # a preempted request kept its retain across the requeue
                # (anti-thrash) — only first admission takes a new hold
                # reprolint: ownership-transfer — the hold moves onto the
                # request (adapter_retained); _drop_retain releases it at
                # finish/failure, never at preemption
                self.model.store.retain(r.adapter)
                r.adapter_retained = True
            r.dec_slot = slot
            r.state = State.PREFILL
            # queue wait ends at the first admission; a preempted
            # request's re-admission is not a new wait
            admitted.append((r, r.t_admit is None))
            if r.t_admit is None:
                r.t_admit = self.clock.now()
            if self.spec:
                kind = ("suffix" if (self.spec.drafter == "suffix"
                                     and r.draft_suffix is not None)
                        else "ngram")
                self._spec[slot] = (
                    make_drafter(kind, ngram_n=self.spec.ngram_n,
                                 suffix=r.draft_suffix),
                    AdaptiveK(self.spec))
            self.waiting.remove(r)
            if self.suffix_prefill:
                # suffix-only prefill: shared-prefix K/V is read through the
                # full block table; this chunk's writes land at positions
                # >= cached_len, so they can never touch a shared block.
                # A COLD start (no reused prefix) keeps the cheaper prompt-
                # local attention path (cached_len=None) — there is nothing
                # in the pool for its first chunk to read back.
                r.prefilled = reused
                suffix = r.prompt_len - r.prefilled
                take = (suffix if budget_left is None
                        else min(suffix, budget_left))
                self.metrics.reused_prefix_tokens += reused
                if take <= 0:
                    # an earlier try_admit this tick shed the prefix this
                    # request's suffix was priced against, draining the
                    # budget: park it as a partial prefill (its slot and
                    # blocks are held) instead of assembling a dead row
                    self.prefilling[slot] = r
                    continue
                if budget_left is not None:
                    budget_left -= take
                pf_reqs.append(flow.PFReq(
                    tokens=r.prompt[r.prefilled:r.prefilled + take],
                    rid=r.rid, slot=aslot, aux_embed=r.aux_embed,
                    block_table=(self.cachemgr.table_of(slot) if reused
                                 else self.cachemgr.write_table_of(slot)),
                    cached_len=r.prefilled if reused else None))
                chunks.append((r, take, r.prefilled + take >= r.prompt_len))
            else:
                # full-prompt recompute (dense layout, or hybrid models
                # whose SSM state must see every prompt token): prefill
                # writes through write_table_of — shared prefix entries are
                # nulled so prefill never rewrites blocks it doesn't own
                r.prefilled = 0
                pf_reqs.append(flow.PFReq(
                    tokens=r.prompt, rid=r.rid, slot=aslot,
                    aux_embed=r.aux_embed,
                    block_table=(self.cachemgr.write_table_of(slot)
                                 if self.paged else None)))
                chunks.append((r, r.prompt_len, True))
        return admitted

    # ---------------------------------------------- preemption (over-admit)
    def _grow_or_preempt(self, slot: int, r: Request, L: int, n: int,
                         pinned: frozenset) -> int:
        """``prepare_write`` with the over-admission backstop.  A short grow
        (or a copy-on-write that finds the pool dry) that cannot cover even
        the committed token at ``L`` means a lent-out reservation came due:
        preempt the lowest-priority resident and retry.  ``pinned`` slots
        hold prefill rows already assembled this tick and must survive; the
        requesting slot competes on priority like everyone else and preempts
        itself when it IS the lowest."""
        while True:
            try:
                writable = self.cachemgr.prepare_write(slot, L, n)
            except OutOfBlocksError:
                writable = 0
            if writable >= 1:
                return writable
            victim = self._pick_victim(exclude=pinned)
            if victim is None or victim == slot:
                self._preempt(slot)
                return 0
            self._preempt(victim)

    def _pick_victim(self, exclude: frozenset) -> Optional[int]:
        """Lowest-priority resident.  Priority CLASS dominates — batch
        residents are evicted before standard, interactive last ("batch
        lends first, interactive preempts last") — then, within a class:
        latest arrival, tie-broken toward the lowest speculative acceptance
        rate (the row burning the most verify compute per emitted token),
        then the latest rid for determinism.  All-standard traffic (the
        default) makes the class rank a constant and reproduces the
        pre-class victim order exactly."""
        cands = [(s, r) for s, r in list(self.active.items())
                 + list(self.prefilling.items()) if s not in exclude]
        if not cands:
            return None

        def badness(item):
            s, r = item
            ctl = self._spec.get(s)
            acc = ctl[1].rate if ctl is not None else 0.0
            return (r.class_rank, r.arrival, -acc, r.rid)

        return max(cands, key=badness)[0]

    def _preempt(self, slot: int):
        """Recompute preemption (vLLM-style, cheap here because suffix-only
        prefill re-adopts any of the victim's published blocks that stay
        index-resident — its own full blocks are its resume capital): roll
        the victim's emitted tokens into its prompt, free its blocks
        (decref-only through shared/index-held blocks), and requeue it at
        the HEAD of ``waiting``.  ``arrival`` and ``t_first_token`` are kept
        — preemption shows up as decode latency, never as a reset — and
        re-prefill of prompt+output re-derives the exact greedy state, so
        outputs stay byte-identical to the conservative gate."""
        r = self.active.pop(slot, None)
        if r is None:
            r = self.prefilling.pop(slot)
        if len(r.output) > r.rolled:
            # only the not-yet-rolled tail: a request preempted twice must
            # not duplicate its first resume's tokens inside the prompt
            r.prompt = np.concatenate(
                [np.asarray(r.prompt),
                 np.asarray(r.output[r.rolled:],
                            np.asarray(r.prompt).dtype)])
            r.rolled = len(r.output)
        r.prefilled = 0
        r.dec_slot = -1
        r.state = State.WAITING
        r.preemptions += 1
        r.recount_pending = True
        self._spec.pop(slot, None)
        if self.kv_tiering:
            # tiered KV memory: swap the victim's blocks to host instead of
            # recomputing when the modeled transfer beats suffix recompute.
            # Must run BEFORE free() — the D2H gather reads the table.
            r.swap_sid = self._maybe_swap_out(slot, r)
        self.cachemgr.free(slot)
        # the victim KEEPS its adapter retain: it resumes from the head of
        # the waiting queue, and evicting (or pool-shedding) its adapter
        # just to swap it straight back in would be pure thrash
        self.waiting.insert(0, r)
        self.metrics.preemptions += 1

    def _maybe_swap_out(self, slot: int, r: Request) -> Optional[int]:
        """Price one preemption victim's swap with the virtual cost model
        and take it only when it wins; every decision is appended to
        ``swap_decisions`` so the bench can replay the rule analytically.
        ``recompute_tokens`` is the victim's committed tokens minus the
        blocks OTHER holders keep device-resident through the free (sibling
        tables, multi-adopter index entries) — what suffix prefill would
        actually recompute if the remaining index-only blocks are shed
        before re-admission, which is precisely the memory-pressure regime
        preemption runs in."""
        m = self.cachemgr
        nb = m.swap_payload_blocks(slot)
        tokens = int(m.lens[slot])
        surviving = m.surviving_blocks(slot, nb)
        recompute = max(tokens - surviving * m.block_size, 0)
        stored = nb * m.host_block_bytes
        chose = nb > 0 and swap_beats_recompute(stored, recompute,
                                                self._cost)
        sid = m.swap_out(slot) if chose else None
        self.swap_decisions.append({
            "rid": r.rid, "tokens": tokens, "blocks": nb,
            "stored_bytes": stored, "recompute_tokens": recompute,
            "chose_swap": bool(chose), "swapped": sid is not None})
        if sid is None:
            self.metrics.kv_swap_skips += 1
        return sid

    def _drop_swap(self, r: Request):
        """Release the request's host swap set (if any) exactly once — on
        failure paths that retire the request before re-admission ever
        consumes it."""
        if r.swap_sid is not None and self.paged:
            self.cachemgr.drop_swap(r.swap_sid)
            r.swap_sid = None

    def _scatter_verify(self, slot: int, r: Request, logits: np.ndarray,
                        draft: Optional[np.ndarray], now: float):
        """Greedy acceptance for one verify chunk: keep the longest draft
        prefix matching the model's argmax plus the bonus token, then roll
        the paged cache back past the accepted length (releasing blocks the
        rejected drafts transiently occupied)."""
        if draft is None:
            draft = np.zeros((0,), np.int64)
        L = int(self.cachemgr.lens[slot])
        n_acc, emitted = accept_greedy(draft, logits)
        # exactness clamps: never emit past max_new_tokens, stop at eos —
        # the same cuts plain greedy decode would have made tick by tick
        emitted = emitted[:r.max_new_tokens - len(r.output)]
        if r.eos_token >= 0 and r.eos_token in emitted:
            emitted = emitted[:emitted.index(r.eos_token) + 1]
        n_kept = len(emitted)
        t_prev = r.token_times[-1] if r.token_times else now
        r.token_times.extend(spread_token_times(t_prev, now, n_kept))
        r.output.extend(emitted)
        # cache holds K/V for [current, accepted drafts]; the bonus token is
        # the next step's input.  Rejected draft positions are rolled back,
        # then the accepted INPUT tokens ([current] + accepted drafts) are
        # committed — which may fill and publish blocks into the dedup index
        self.cachemgr.truncate(slot, L + n_kept)
        self.cachemgr.commit_tokens(
            slot, [int(self._last_tokens[slot])] + list(emitted[:-1]))
        self._last_tokens[slot] = emitted[-1]
        self.metrics.decode_tokens += n_kept
        if len(draft):
            self.metrics.spec_drafted += len(draft)
            self.metrics.spec_accepted += n_acc
            self.metrics.spec_steps += 1
            self._spec[slot][1].update(len(draft), n_acc)
        self._maybe_finish(r, now)

    def _apply_trainer(self, tr: MixedLoraTrainer):
        with spans.span("serve.apply", trainer=tr.name):
            store = self.model.store
            mask = store.slot_mask([tr.name])
            new_bank, self.opt_state = self.apply_step(self.grad_accum,
                                                       self.opt_state,
                                                       store.bank, mask)
            store.set_bank(new_bank)
            # the bank slot now holds newer weights than the host archive /
            # pool payload — sync happens lazily at the next shed or
            # eviction
            store.mark_dirty(tr.name)
            inv = 1.0 - mask
            self.grad_accum = tree_mask_slots(self.grad_accum, inv)

    def _maybe_finish(self, r: Request, now: float):
        done_len = len(r.output) >= r.max_new_tokens
        eos = r.eos_token >= 0 and r.output and r.output[-1] == r.eos_token
        ctx_full = self.cachemgr.lens[r.dec_slot] + 1 >= self.ecfg.s_max
        if done_len or eos or ctx_full:
            r.state = State.DONE
            r.t_finish = now
            self.active.pop(r.dec_slot, None)
            self._spec.pop(r.dec_slot, None)
            self.cachemgr.free(r.dec_slot)
            self._drop_retain(r)
            self.finished.append(r)

    def _drop_retain(self, r: Request):
        """Drop the request's adapter hold (if it took one) exactly once —
        at finish or failure, never at preemption."""
        if r.adapter and r.adapter_retained:
            self.model.store.release(r.adapter)
            r.adapter_retained = False

    # ------------------------------------------------------------------
    def run(self, max_ticks: int = 100000, until_drained: bool = True):
        """Run until all inference requests finish and trainers complete."""
        for _ in range(max_ticks):
            busy = self.tick()
            drained = (not self.waiting and not self.active
                       and not self.prefilling and not self.future
                       and not self.trainers_pending())
            if until_drained and drained:
                break
            if not busy and not until_drained:
                break
        for tr in self.trainers.values():
            if tr.force_apply_pending():
                self._apply_trainer(tr)
        self.metrics.elapsed = self.clock.now()
        return self.metrics

    @property
    def all_requests(self) -> List[Request]:
        return self.finished + list(self.active.values()) \
            + list(self.prefilling.values()) + self.waiting + self.future
