"""A cell of the real benchmark cut to a size the CPU runs in seconds: the
same files, traffic generator, engine path and reference, at 2 layers of
width 256 over a 512-token vocabulary."""
import copy
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

TINY_MODEL = dict(hidden_size=256, intermediate_size=512,
                  num_attention_heads=8, num_key_value_heads=4,
                  num_hidden_layers=2, vocab_size=512)
TINY_MIX = dict(system_prompt_tokens=32, preroll_s=1.0,
                user_tokens=dict(median=24, sigma=0.7, min=8, max=64),
                output_tokens=dict(median=8, sigma=0.5, min=4, max=16))
TINY_TRAINER = dict(rows=8, tokens=dict(median=24, sigma=0.5, min=16,
                                        max=48))
# limits at this size, between its sound readings on the CPU and the
# least of the float8 control's and the planted faults' (three seeds):
# token gap 0.001 / 0.006, loss gap 0.0011 / 0.0068, first gradient's norm
# gap 0.0017 / 0.10 (half the rows), its difference 0.0098 / 0.093
# (control), change 0.006 / 1 (state unchanged)
TINY_LIMITS = dict(token_gap=0.02, ft_loss_gap=0.002, ft_grad_gap=0.02,
                   ft_grad_diff=0.03, ft_change_gap=0.1)
TINY_ENGINE = dict(capacity=4, pf_capacity=2, prefill_chunk=32, s_max=128,
                   block_size=16, n_blocks=48, row_buckets=[2],
                   seq_buckets=[32, 64])


def tiny(cell_name: str):
    """The named cell of BENCHMARK.json at the tiny size."""
    from perfbench import harness
    cell = harness.load_cell(cell_name)
    cell = copy.deepcopy(cell, {id(cell.arch): cell.arch})
    cell.config.update(TINY_MODEL)
    cell.traffic.update(TINY_MIX)
    if "trainer" in cell.traffic:
        cell.traffic["trainer"].update(TINY_TRAINER)
    cell.setup["engine"].update(TINY_ENGINE)
    cell.setup["rate_rps"] = 12.0
    cell.setup["sample_requests"] = 4
    cell.setup["limits"] = {k: TINY_LIMITS[k] for k in cell.setup["limits"]}
    return cell


@pytest.fixture
def tiny_cell():
    return tiny
