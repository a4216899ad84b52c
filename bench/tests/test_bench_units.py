"""The benchmark's yardstick on small inputs, CPU only: the trace
reduction, the metric arithmetic, the traffic generators and finding a
mix by name."""
import json
from types import SimpleNamespace

import pytest

from bench import layers, stats, trace
from bench.breakdown import breakdown
from bench.model import load_config, paged_attention_bytes
from bench.spec import BENCH_DIR, generator, load_mix


def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _planes():
    """One device (ops and programs) and the host's spans, in ns."""
    dev = SimpleNamespace(name="/device:TPU:0", lines=[
        SimpleNamespace(name="XLA Modules", events=[
            _ev("jit_paged_decode_step_device(1)", 100, 300),
            _ev("jit_prefill_kv_chunk(2)", 600, 200)]),
        SimpleNamespace(name="XLA Ops", events=[
            _ev("fusion.1", 100, 50),
            _ev("paged_attention", 150, 100),
            _ev("fusion.2", 220, 80),          # overlaps the kernel
            _ev("fusion.3", 600, 200)])])
    host = SimpleNamespace(name="/host:CPU", lines=[
        SimpleNamespace(name="python", events=[
            _ev("bench:window", 0, 1000),
            _ev("bench:step", 350, 300),
            _ev("bench:flush_wait", 400, 100),
            _ev("other", 0, 10)])])
    return [SimpleNamespace(name="/host:metadata", lines=[]), dev, host]


def test_reduction_union_idle_and_gaps():
    red = trace.reduce_planes(_planes())
    assert red.window() == (0.0, 1000.0)
    assert red.busy(0) == [[100.0, 300.0], [600.0, 800.0]]
    assert red.busy_ns(0, 0, 1000) == 400.0
    assert red.idle_gaps(0, 0, 1000) == [[0.0, 100.0], [300.0, 600.0],
                                         [800.0, 1000.0]]
    # the innermost benchmark span open at a gap's middle names it
    assert red.span_at(450) == "flush_wait"
    assert red.span_at(550) == "step"
    assert red.span_at(900) == "client"
    bd = breakdown(red, 0, 1000)
    assert bd["idle_gaps"][0] == ["flush_wait", 300 / 1e9]
    assert bd["device_ops"][0][0] == "prefill/fusion.3"
    names = dict(bd["device_ops"])
    assert names["paged_attention/paged_attention"] == pytest.approx(1e-7)


def test_name_table():
    assert layers.program_layer("jit_paged_decode_step_device(7)") == "decode"
    assert layers.program_layer("jit__insert_prefill") == "prefill"
    assert layers.program_layer("jit__gather_swap_body") == "swap"
    assert layers.program_layer("jit_hidden") == "other"
    assert layers.kernel_of("paged_attention") == "paged_attention"
    assert layers.kernel_of("fusion.4") is None
    red = trace.reduce_planes(_planes())
    assert layers.program_time(red, 0, "decode", 0, 1000) == (3e-7, 1)
    assert layers.kernel_time(red, 0, "paged_attention", 0, 1000) == (1e-7, 1)


def test_union_and_slope():
    assert stats.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert stats.union_length([]) == 0
    assert stats.slope([0, 1, 2], [1, 3, 5]) == pytest.approx(2.0)


def _turn(due, times, done=True):
    return {"due": due, "times": times, "done": done,
            "end": times[-1] if done and times else None}


def test_ttft_counts_unanswered_turns_with_their_wait():
    turns = [_turn(1.0, [1.5, 1.6]), _turn(2.0, [], done=False),
             _turn(0.5, [0.9]),                      # due before the window
             _turn(9.5, [10.5])]                     # answered after it
    ttft = stats.ttft_samples(turns, 1.0, 10.0)
    assert ttft == pytest.approx([0.5, 8.0, 0.5])
    assert stats.percentile(ttft, 50) == pytest.approx(0.5)
    assert stats.percentile([], 90) is None


def test_tbt_over_the_whole_window_with_open_gaps():
    turns = [_turn(0.0, [0.5, 1.0, 1.2, 1.2, 3.0]),
             _turn(0.0, [2.0, 2.5], done=False),     # stalled at the end
             _turn(0.0, [3.5, 4.5])]                 # gap crosses the end
    gaps = stats.tbt_samples(turns, 1.0, 4.0)
    assert sorted(gaps) == pytest.approx(sorted([0.2, 0.0, 1.8, 0.5, 1.5,
                                                 0.5]))
    assert stats.tokens_in(turns, 1.0, 4.0) == 7
    mean = sum(gaps) / len(gaps)
    assert mean == pytest.approx(4.5 / 6)


def test_live_byte_roofline_counts_live_context_only():
    hp = load_config(BENCH_DIR / "configs" / "qwen2-1.5b.json")
    # two rows of 100 and 300 live tokens: K and V at 2 heads x 128 x 2 B,
    # plus q and out at 12 heads x 128 x 2 B per row
    b = paged_attention_bytes(hp, [100, 300])
    assert b == 2 * 2 * 128 * 2 * 400 + 2 * 12 * 128 * 2 * 2
    from bench.metrics import paged_attn_roofline
    red = trace.reduce_planes(_planes())
    ctx = SimpleNamespace(
        hp=hp, trace=red, lo=0, hi=1000,
        peaks={"hbm_bytes_per_s": 1e12, "bf16_flops": 1e15},
        host_window=(0.0, 10.0),
        spans=SimpleNamespace(decode_rows=[(1.0, [100, 300])]))
    # one call of 100 ns against a bound of b / 1e12 s
    assert paged_attn_roofline.read(ctx) == pytest.approx(
        b / 1e12 / 1e-7 * 100)


@pytest.mark.parametrize("mix", ["chat-switch", "bulk"])
def test_generators_are_deterministic_per_seed(mix):
    m = load_mix(mix)
    gen = generator(m)

    def first(seed, n=12):
        t = gen.build(m, seed, 1000)
        out = []
        while len(out) < n:
            due = t.next_due()
            for s in t.pop_due(due):
                out.append((s.sid, s.turn, s.max_tokens, s.due, s.prompt))
                t.finished(s.sid, s.turn, due + 1.0)
        return out

    big = 2 ** 31 + 99
    assert first(big) == first(big)
    assert first(big) != first(big + 1)


def test_seeds_reorder_the_same_sizes():
    m = load_mix("chat-switch")
    gen = generator(m)
    sent = []
    for seed in (1, 2 ** 31 + 7):
        t = gen.build(m, seed, 1000)
        sent.append(t.pop_due(600.0))
    # the same sessions in progress, in another order
    live = [sorted(tuple(s.max_tokens for s in ss if s.due == 0)) for ss in sent]
    assert live[0] == live[1] and len(live[0]) > 1
    assert ([s.max_tokens for s in sent[0] if s.due == 0]
            != [s.max_tokens for s in sent[1] if s.due == 0])
    # and the same new sessions at the same times
    new = [[(s.due, s.max_tokens, len(s.prompt)) for s in ss if s.due > 0]
           for ss in sent]
    assert new[0] == new[1] and len(new[0]) > 10


def test_closed_loop_seeds_reorder_each_block_of_clients():
    m = load_mix("bulk")
    gen = generator(m)
    n = m["clients"]
    firsts = []
    for seed in (1, 2 ** 31 + 7):
        t = gen.build(m, seed, 1000)
        firsts.append(t._shapes[:3 * n])
    assert firsts[0] != firsts[1]
    for i in range(0, 3 * n, n):
        assert sorted(firsts[0][i:i + n]) == sorted(firsts[1][i:i + n])


def test_arrivals_run_on_past_any_window():
    m = load_mix("chat-switch")
    t = generator(m).build(m, 5, 1000)
    sent = t.pop_due(3600.0)
    new = [s for s in sent if s.due > 0]
    # a Poisson stream at rate_per_s for the whole hour, not a fixed
    # horizon's worth
    assert abs(len(new) / 3600.0 - m["rate_per_s"]) < 0.3 * m["rate_per_s"]
    assert max(s.due for s in new) > 3000.0
    assert t.next_due() > 3600.0


def test_a_new_mix_is_found_by_name(tmp_path):
    mix = load_mix("chat-switch")
    mix.update(rate_per_s=0.5, session_s=4)
    (tmp_path / "my-mix.json").write_text(json.dumps(mix))
    found = load_mix("my-mix", tmp_path)
    assert found["name"] == "my-mix" and found["rate_per_s"] == 0.5
    t = generator(found).build(found, 3, 500)
    first = t.pop_due(0.0)
    assert len(first) == 2 and all(max(s.prompt) < 500 for s in first)


def test_swap_shapes_cover_every_bucket_once():
    from bench.harness import _swap_shapes
    from bench.traffic.base import pow2
    seen = set()
    for lens in _swap_shapes(64):
        assert all(n >= 1 for n in lens) and sum(lens) <= 64
        key = (pow2(len(lens)), pow2(sum(lens)), pow2(max(lens)))
        assert key not in seen
        seen.add(key)
    # every bucket a chunk of <= 64 blocks in n runs can reach
    want = {(pow2(n), pow2(t), pow2(m)) for n in range(1, 65)
            for t in range(n, 65) for m in range(-(-t // n), t - n + 2)}
    assert seen == want


def test_reused_prefills_reach_each_bucket_pair():
    from bench.traffic.base import pow2, reused_prefills
    shapes = reused_prefills(4096, 16)
    pairs = {(c, max(pow2(p // 16) * 16, pow2(p + c))) for c, p, _ in shapes}
    # a follow-up of n new tokens after p cached ones, p + n <= 4096
    for p in range(16, 4096, 16):
        for n in (1, 17, 100, 513, 2000):
            if p + n <= 4096:
                c = max(16, pow2(n))
                assert (c, max(pow2(p // 16) * 16, pow2(p + c))) in pairs
