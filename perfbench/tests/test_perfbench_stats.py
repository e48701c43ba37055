"""Percentiles, censored time to first token, the SLO and the FLOPs count
on hand-made numbers."""
import numpy as np
import pytest

from perfbench import stats
from perfbench.arch import llama
from perfbench.stats import Served


def test_ttft_counts_a_request_still_waiting():
    reqs = [Served(0.0, [0.5, 0.6]), Served(1.0, [1.2]), Served(2.0, [])]
    assert list(stats.ttfts(reqs, w1=5.0)) == pytest.approx([0.5, 0.2, 3.0])
    late = Served(1.0, [7.0])
    assert stats.ttfts([late], w1=5.0)[0] == pytest.approx(4.0)


def test_due_and_gaps_in_window():
    reqs = [Served(-1.0, [0.5, 1.0, 3.0]), Served(2.0, [2.5, 2.7, 9.0])]
    assert len(stats.due(reqs, 0.0, 5.0)) == 1
    g = stats.token_gaps(reqs, 0.0, 5.0)
    assert sorted(g) == pytest.approx([0.2, 0.5, 2.0])


def test_percentile():
    x = np.arange(1, 101, dtype=float)
    assert stats.percentile(x, 90) == pytest.approx(90.1)
    assert stats.percentile(np.zeros(0), 90) is None


@pytest.mark.parametrize("served,ok", [
    (Served(0.0, [1.0, 1.1, 1.2]), True),
    (Served(-2.0, [4.5, 4.6]), False),         # waited past 6 s
    (Served(0.0, []), True),                   # 5 s in, still in time
    (Served(-2.0, []), False),                 # 7 s and no token
    (Served(0.0, [1.0, 1.3, 1.6]), False),     # mean gap 300 ms
    (Served(0.0, [1.0, 1.05, 1.1, 1.15, 2.3]), False),   # one 1.15 s gap
    (Served(0.0, [1.0], failed=True), False),
])
def test_slo(served, ok):
    assert stats.meets_slo(served, w1=5.0) is ok


def test_queue_growth():
    assert stats.queue_growth([(t, 2) for t in range(10)]) == \
        pytest.approx(0.0, abs=1e-9)
    assert stats.queue_growth([(t, t) for t in range(11)]) == \
        pytest.approx(10.0)


SPEC = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=128, num_hidden_layers=3, vocab_size=100)


def test_step_flops():
    lin = 2 * (llama.layer_params(SPEC) + llama.lora_params(SPEC, 8))
    att, head = 4 * 64, 2 * 64 * 100
    assert llama.layer_params(SPEC) == 64 * 64 * 2 + 64 * 32 * 2 \
        + 3 * 64 * 128
    assert llama.step_flops(SPEC, 8, [], [], [10]) == \
        pytest.approx(3 * (lin + att * 10) + head)
    pf = 3 * (lin * 4 + att * (4 * 6 + 10)) + head
    assert llama.step_flops(SPEC, 8, [], [(4, 6)], []) == pytest.approx(pf)
    ft = 2 * (3 * (lin * 4 + att * 10) + head * 4)
    assert llama.step_flops(SPEC, 8, [4], [], []) == pytest.approx(ft)


def test_unknown_chip_is_an_error():
    assert stats.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        stats.peaks("cpu")
