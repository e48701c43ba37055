"""The traffic generator: the seed fixes the requests; every seed brings
the same amount of work in another order."""
import json

import numpy as np
import pytest

from perfbench import harness, traffic

MIX = json.loads((harness.HERE / "traffic" / "chat-coserve.json").read_text())


def draw(seed, rate=2.0, horizon=30.0):
    return traffic.make_requests(MIX, 32064, rate, horizon, seed)


def test_same_seed_same_requests():
    a, b = draw(2 ** 31 + 5), draw(2 ** 31 + 5)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x["arrival"] == y["arrival"] and x["adapter"] == y["adapter"]
        assert x["max_new"] == y["max_new"]
        assert np.array_equal(x["prompt"], y["prompt"])


def test_other_seed_other_order_same_work():
    a, b = draw(7), draw(8)
    assert [x["max_new"] for x in a] != [x["max_new"] for x in b]
    for key in ("max_new", "adapter"):
        assert sorted(x[key] for x in a) == sorted(x[key] for x in b)
    assert sorted(len(x["prompt"]) for x in a) == \
        sorted(len(x["prompt"]) for x in b)
    assert a[-1]["arrival"] == pytest.approx(b[-1]["arrival"])


def test_shape_of_the_mix():
    reqs = draw(3, rate=4.0, horizon=100.0)
    sys_len = MIX["system_prompt_tokens"]
    counts = np.bincount([r["adapter"] for r in reqs])
    assert list(counts) == sorted(counts, reverse=True)
    assert counts[0] / counts[-1] == pytest.approx(8, rel=0.2)
    user = np.array([len(r["prompt"]) - sys_len for r in reqs])
    assert np.median(user) == pytest.approx(192, rel=0.05)
    assert user.min() >= 16 and user.max() <= 512
    out = np.array([r["max_new"] for r in reqs])
    assert np.median(out) == pytest.approx(128, rel=0.05)
    by_adapter = {}
    for r in reqs:
        head = r["prompt"][:sys_len]
        assert np.array_equal(by_adapter.setdefault(r["adapter"], head),
                              head)
    gaps = np.diff([0.0] + [r["arrival"] for r in reqs])
    assert gaps.mean() == pytest.approx(0.25, rel=0.05)


def test_train_rows():
    rows = traffic.make_train_rows(MIX, 32064, 11)
    lens = np.array([len(r) for r in rows])
    assert len(rows) == MIX["trainer"]["rows"]
    assert lens.min() >= 64 and lens.max() <= 512
    assert np.median(lens) == pytest.approx(256, rel=0.1)
