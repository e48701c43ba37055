"""The host span recorder (``repro.spans``) and the engine's spans and
counters: the no-op when off, the record tree when on, one ``serve.tick``
tree per stepped tick, the dispatch span under a blocking wrapper, queue
wait across a preemption, padding and KV block counts."""
import gc
import time

import jax
import numpy as np
import pytest

from repro import spans
from repro.configs import get_reduced
from repro.core.lora import LoRAConfig
from repro.core.virtualization import AdapterStore, MixedLoraModel
from repro.data import datasets
from repro.models.schema import init_params
from repro.serving.engine import EngineConfig, UnifiedEngine
from repro.serving.request import Request, State
from repro.training.trainer import MixedLoraTrainer, TrainerConfig

LCFG = LoRAConfig(n_slots=4, r=4)
TICK_CHILDREN = ["serve.plan", "serve.dispatch", "serve.wait",
                 "serve.scatter"]


@pytest.fixture
def recorder():
    """The recorder on for the test, off and empty after it."""
    spans.drain()
    spans.enable()
    yield
    spans.disable()
    spans.drain()


def _engine(trainer=False, **kw):
    cfg = get_reduced("llama3-8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    store = AdapterStore(cfg, LCFG, jax.random.PRNGKey(1))
    store.load_random("serve", jax.random.PRNGKey(2))
    kw = {"capacity": 4, "pf_capacity": 2, "s_max": 96, "virtual_time": True,
          **kw}
    eng = UnifiedEngine(MixedLoraModel(cfg, params, store), EngineConfig(**kw))
    if trainer:
        store.load_random("tr", jax.random.PRNGKey(3))
        rows, ev = datasets.split_eval(
            datasets.alpaca_like(16, vocab=cfg.vocab, seed=0))
        eng.add_trainer(MixedLoraTrainer("tr", store.slot_of("tr"), rows, ev,
                                         TrainerConfig(rows_per_micro=2,
                                                       accum_steps=2,
                                                       epochs=1)))
    return eng


def _requests(n, prompt_len=10, max_new=4, gap=0.05, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, prompt=rng.integers(0, 256, prompt_len)
                    .astype(np.int32), adapter="serve",
                    max_new_tokens=max_new, arrival=gap * i)
            for i in range(n)]


def _children(recs, i):
    return [r for r in recs if r is not None and r[3] == i]


def test_off_is_the_shared_noop():
    spans.disable()
    spans.drain()
    s = spans.span("serve.tick", kind="fwd")
    assert s is spans.OFF and not s
    with spans.span("a") as inner:
        inner.set(x=1)
    assert spans.wrap("w", lambda x: x + 1)(1) == 2
    gc.collect()
    assert spans.drain() == []


def test_tree_attributes_and_drain(recorder):
    with spans.span("outer", rid=[3, 4]) as o:
        with spans.span("inner"):
            pass
        o.set(kind="fwd")
        with spans.span("second"):
            with spans.span("leaf", n=2):
                pass
    assert spans.wrap("wrapped", lambda a, b: a + b,
                      lambda a, b: {"a": a})(2, 3) == 5
    recs = spans.drain()
    names = [r[0] for r in recs]
    assert names == ["outer", "inner", "second", "leaf", "wrapped"]
    parents = [r[3] for r in recs]
    assert parents == [-1, 0, 0, 2, -1]
    assert recs[0][4] == {"rid": [3, 4], "kind": "fwd"}
    assert recs[3][4] == {"n": 2} and recs[4][4] == {"a": 2}
    for name, t0, t1, parent, _ in recs:
        assert t0 <= t1
        if parent >= 0:
            assert recs[parent][1] <= t0 and t1 <= recs[parent][2]
    assert spans.drain() == []


def test_collections_are_recorded(recorder):
    with spans.span("outer"):
        gc.collect(0)       # a young generation: no span
    assert [r[0] for r in spans.drain()] == ["outer"]
    with spans.span("outer"):
        gc.collect()
    recs = spans.drain()
    coll = [r for r in recs if r[0] == "host.gc"]
    assert coll and all(r[3] == 0 for r in coll)
    assert coll[0][4]["generation"] == 2


def test_profiler_turns_the_recorder_on(tmp_path):
    spans.disable()
    spans.drain()
    eng = _engine()
    assert not eng.tick()
    assert spans.span("serve.tick") is spans.OFF
    for r in _requests(2, gap=0.0):
        eng.submit(r)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.tick()
    finally:
        jax.profiler.stop_trace()
    assert spans.drain() == []      # the trace holds the spans, not memory
    eng.tick()          # the first tick after the trace turns it off
    assert spans.span("serve.tick") is spans.OFF
    pd = jax.profiler.ProfileData.from_file(
        str(next(tmp_path.rglob("*.xplane.pb"))))
    got = {ev.name: dict(ev.stats) for p in pd.planes
           if p.name.startswith("/host:") for line in p.lines
           for ev in line.events if ev.name.startswith("serve.")}
    assert set(got) >= {"serve.tick", *TICK_CHILDREN, "serve.decide",
                        "serve.admit", "serve.assemble", "serve.readback"}
    assert got["serve.tick"]["kind"] == "fwd"
    assert got["serve.admit"]["admissions"] == 2
    assert got["serve.assemble"]["step_tokens"] == 20


def test_every_stepped_tick_holds_its_phases(recorder):
    eng = _engine(trainer=True)
    for r in _requests(4, gap=1.0):   # the last ones after the fine-tune
        eng.submit(r)
    eng.run(max_ticks=400)
    recs = spans.drain()
    ticks = [i for i, r in enumerate(recs) if r[0] == "serve.tick"]
    stepped = [i for i in ticks if recs[i][4].get("kind")]
    assert stepped and len(stepped) == eng.metrics.steps
    kinds = {recs[i][4]["kind"] for i in stepped}
    assert kinds == {"fwd", "grad"}
    for i in stepped:
        kids = _children(recs, i)
        assert [k[0] for k in kids if k[0] != "host.gc"] == TICK_CHILDREN
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1]
        plan = recs.index(next(k for k in kids if k[0] == "serve.plan"))
        assert [k[0] for k in _children(recs, plan)
                if k[0] != "host.gc"] == ["serve.decide", "serve.admit",
                                          "serve.assemble"]
        dispatch = next(k for k in kids if k[0] == "serve.dispatch")
        assert dispatch[4]["kind"] == recs[i][4]["kind"]
    scatter_kids = {k[0] for i in stepped for k in
                    _children(recs, recs.index(next(
                        k for k in _children(recs, i)
                        if k[0] == "serve.scatter")))}
    assert scatter_kids >= {"serve.readback", "serve.apply"}
    # span attributes carry the tick's counts
    total = {}
    for r in recs:
        if r is not None and r[0] in ("serve.admit", "serve.assemble",
                                      "serve.tick"):
            for k, v in r[4].items():
                if isinstance(v, (int, float)):
                    total[k] = total.get(k, 0) + v
    assert total["admissions"] == 4
    assert total["queue_wait_s"] == pytest.approx(
        sum(r.t_admit - r.arrival for r in eng.all_requests))
    assert 0 < total["step_tokens"] <= total["step_positions"]
    assert 0 < total["kv_blocks_used"] <= total["kv_blocks_reserved"]
    admitted = [rid for r in recs if r is not None and r[0] == "serve.admit"
                for rid in r[4]["rid"]]
    assert sorted(admitted) == [0, 1, 2, 3]


def test_dispatch_closes_before_a_blocking_wrapper(recorder):
    eng = _engine()
    inner = eng.forward_step
    hold = 0.05

    def blocking(*a):
        res = jax.block_until_ready(inner(*a))
        time.sleep(hold)
        return res
    eng.forward_step = blocking
    for r in _requests(2):
        eng.submit(r)
    for _ in range(3):
        eng.tick()
    recs = spans.drain()
    for i, r in enumerate(recs):
        if r[0] != "serve.tick" or not r[4].get("kind"):
            continue
        kids = {k[0]: k for k in _children(recs, i)}
        d, w = kids["serve.dispatch"], kids["serve.wait"]
        assert d[2] + hold <= w[1]
        assert w[2] - w[1] < hold


def test_queue_wait_is_stamped_once_across_preemption(recorder):
    # three 3-block-life requests in a 7-block pool under over-admission:
    # lent reservations come due mid-decode and preempt
    eng = _engine(s_max=64, paged=True, block_size=16, n_blocks=8,
                  over_admit=2.0)
    rng = np.random.default_rng(11)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, 8).astype(np.int32),
                    adapter="serve", max_new_tokens=40, arrival=0.1 * i)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    first = {}
    for _ in range(5000):
        busy = eng.tick()
        for r in reqs:
            if r.t_admit is not None:
                first.setdefault(r.rid, r.t_admit)
        if not busy or len(eng.finished) == 3:
            break
    assert eng.metrics.preemptions >= 1
    assert all(r.state is State.DONE for r in reqs)
    assert any(r.preemptions for r in reqs)
    assert {r.rid: r.t_admit for r in reqs} == first
    admits = [r[4] for r in spans.drain() if r[0] == "serve.admit"]
    assert sum(len(a["rid"]) for a in admits) > 3    # re-admissions
    assert sum(a["admissions"] for a in admits) == 3
    assert sum(a["queue_wait_s"] for a in admits) == pytest.approx(
        sum(r.t_admit - r.arrival for r in reqs))


def _assembled(eng):
    """The step tokens and positions of the engine's next tick."""
    spans.drain()
    eng.tick()
    a, = [r[4] for r in spans.drain() if r[0] == "serve.assemble"]
    return a["step_tokens"], a["step_positions"]


def test_padding_counts_match_the_buckets(recorder):
    # default buckets: rows (1, 2, 4, ...), sequences (16, 32, ...) padded
    # to the 8-token tile; the decode bucket is the 4-row capacity
    eng = _engine()
    a, b = _requests(2, prompt_len=10, max_new=3, gap=0.0)
    b.prompt = b.prompt[:7]
    eng.submit(a)
    eng.submit(b)
    # prefill: 2 rows of 10 and 7 tokens in (2, 16)
    assert _assembled(eng) == (17, 2 * 16)
    # decode: 2 live rows in the 4-row bucket
    assert _assembled(eng) == (2, 4)


def test_kv_blocks_used_within_reserved(recorder):
    eng = _engine(block_size=16)
    for r in _requests(3, prompt_len=20, max_new=40):
        eng.submit(r)
    for _ in range(200):
        if not eng.tick():
            break
    steps = [(r[4]["kv_blocks_used"], r[4]["kv_blocks_reserved"])
             for r in spans.drain()
             if r is not None and r[0] == "serve.tick" and r[4].get("kind")]
    assert steps and all(0 < u <= v for u, v in steps)
    # early ticks hold reservations for tokens not yet written
    assert any(u < v for u, v in steps)
