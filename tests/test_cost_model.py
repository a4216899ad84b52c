"""I/O cost model + trace generators."""
from types import SimpleNamespace

import numpy as np
import pytest

from repro.data.priority import PriorityTrace
from repro.data.sharegpt import sample_conversations, trace_stats
from repro.io.cost_model import (A10_PCIE4, IterationCostModel,
                                 dispatch_time_us, exec_time_us,
                                 serving_hardware, transfer_time_us)


def test_small_transfers_are_dispatch_bound():
    """Paper Fig. 3: a 128 KB per-block copy is dominated by dispatch."""
    t = transfer_time_us(A10_PCIE4, 128 * 1024, h2d=False)
    d = dispatch_time_us(A10_PCIE4)
    assert d / t > 0.85


def test_large_transfers_amortize_dispatch():
    nbytes = 20 * 128 * 1024                       # a ~20-block group
    t = transfer_time_us(A10_PCIE4, nbytes, h2d=False)
    d = dispatch_time_us(A10_PCIE4)
    assert d / t < 0.65
    # grouped moves the same bytes faster than per-block
    per_block = 20 * transfer_time_us(A10_PCIE4, 128 * 1024, h2d=False)
    assert t < per_block / 3


def test_bandwidth_ramp_monotone():
    xs = [exec_time_us(A10_PCIE4, n, True) / max(n, 1)
          for n in (16 * 1024, 64 * 1024, 320 * 1024, 1 << 20)]
    assert all(a >= b - 1e-12 for a, b in zip(xs, xs[1:]))


def test_iteration_cost_scales():
    m = IterationCostModel(A10_PCIE4, model_params=8e9,
                           kv_bytes_per_token=131072)
    t1 = m.decode_iter_us(1, 1000)
    t2 = m.decode_iter_us(64, 64000)
    assert t2 > t1
    assert m.prefill_us(4096) > m.prefill_us(128)
    assert m.decode_iter_us(0, 0) == 0.0


def test_sharegpt_stats_match_paper_shape():
    convs = sample_conversations(500, seed=0)
    s = trace_stats(convs)
    assert 4.0 < s["mean_turns"] < 7.0              # paper: 5.5
    assert 0.7 < s["multi_turn_frac"] < 0.86        # paper: 78%
    # Poisson arrivals at ~1 req/s
    arr = [c.arrival_s for c in convs]
    rate = len(arr) / (arr[-1] - arr[0])
    assert 0.8 < rate < 1.25


def test_priority_trace_reproducible():
    t1 = PriorityTrace("markov", 0.05, seed=3)
    t2 = PriorityTrace("markov", 0.05, seed=3)
    ids = list(range(20))
    for _ in range(200):
        t1.step(ids, ids[:4])
        t2.step(ids, ids[:4])
    assert all(t1.priority(i) == t2.priority(i) for i in ids)


@pytest.mark.parametrize("platform,kind,expect", [
    ("cpu", "cpu", "preset"),                 # the interpret-mode test path
    ("tpu", "TPU v5 lite", "TPUv5e-host"),
    ("tpu", "TPU v9 imaginary", ValueError),  # unknown kind: no default
    ("gpu", "NVIDIA A10", ValueError),
])
def test_serving_hardware_by_device_kind(platform, kind, expect):
    """A real-mode engine's cost model comes from the device it serves
    on: the configured preset on the CPU, the ``DEVICE_KINDS`` entry on
    an accelerator, and an error for a kind the table lacks."""
    dev = SimpleNamespace(platform=platform, device_kind=kind)
    if expect is ValueError:
        with pytest.raises(ValueError, match="no cost model"):
            serving_hardware(dev, A10_PCIE4)
    elif expect == "preset":
        assert serving_hardware(dev, A10_PCIE4) is A10_PCIE4
    else:
        assert serving_hardware(dev, A10_PCIE4).name == expect
