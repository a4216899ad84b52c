"""Staged swap data plane (ISSUE 3): run-coalesced gather/scatter KV
integrity, donation/rebind safety, chunked dispatch semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache.paged import PagedPools, PoolSpec
from repro.kernels import ops
from repro.kernels.block_copy import runs_to_indices, split_runs, trim_runs

BS = 8


def _pools(nb=16, ncpu=24, layers=2, heads=2, dim=8):
    spec = PoolSpec(n_layers=layers, n_kv_heads=heads, head_dim=dim,
                    block_size=BS, num_gpu_blocks=nb, num_cpu_blocks=ncpu)
    pools = PagedPools(spec)
    pools.gpu = jax.random.normal(
        jax.random.PRNGKey(7), pools.gpu.shape).astype(jnp.bfloat16)
    return pools


def test_cpu_pool_stores_bf16_bit_pattern():
    """uint16 host pool: half the float32 footprint, bit-exact round trip."""
    pools = _pools()
    assert pools.cpu.dtype == np.uint16
    assert pools.cpu.nbytes * 2 == pools.cpu.astype(np.float32).nbytes
    assert pools.cpu_bf16().dtype == jnp.bfloat16


def test_staged_round_trip_bit_exact_scattered_runs():
    pools = _pools()
    snap = np.asarray(pools.gpu)
    runs = [(1, 3), (6, 2), (11, 1)]
    blocks = runs_to_indices(runs)
    cpu_ids = [5, 0, 9, 2, 17, 21]                  # scattered on purpose
    pools.copy_out_staged(runs, cpu_ids)
    before = pools.gpu
    pools.gpu = jnp.zeros_like(pools.gpu)
    pools.copy_in_staged(cpu_ids, runs)
    got = np.asarray(pools.gpu)
    np.testing.assert_array_equal(got[:, :, blocks], snap[:, :, blocks])
    # donation safety: the rebind installed a NEW owner-of-record array
    assert pools.gpu is not before
    # untouched blocks of the donated pool must be preserved (zeros here)
    other = [b for b in range(16) if b not in blocks]
    assert not np.any(got[:, :, other]), "scatter leaked into other blocks"


def test_staged_matches_host_baseline_bitwise():
    """Same blocks through the staged path and the legacy host-mediated
    path must produce identical uint16 CPU pools and GPU pools."""
    p1, p2 = _pools(), _pools()
    runs = [(0, 2), (5, 4)]
    blocks = runs_to_indices(runs)
    cpu_ids = list(range(len(blocks)))
    p1.copy_out_staged(runs, cpu_ids)
    p2.copy_out(blocks, cpu_ids)
    np.testing.assert_array_equal(p1.cpu, p2.cpu)
    p1.gpu = jnp.zeros_like(p1.gpu)
    p2.gpu = jnp.zeros_like(p2.gpu)
    p1.copy_in_staged(cpu_ids, runs)
    p2.copy_in(cpu_ids, blocks)
    np.testing.assert_array_equal(np.asarray(p1.gpu), np.asarray(p2.gpu))


def test_staged_round_trip_partial_last_block():
    """A context ending mid-block: the whole last block round-trips (the
    tail beyond the context is masked by attention, but the engine's
    read_tokens view of the valid prefix must be bit-exact)."""
    pools = _pools()
    n_tokens = 2 * BS + 3                           # partial third block
    L, H, D = 2, 2, 8
    rng = np.random.RandomState(0)
    k = rng.randn(L, n_tokens, H, D).astype(np.float32)
    v = rng.randn(L, n_tokens, H, D).astype(np.float32)
    block_ids = [4, 9, 2]
    pools.write_tokens(block_ids, 0, k, v)
    k0, v0 = pools.read_tokens(block_ids, n_tokens)
    runs = [(4, 1), (9, 1), (2, 1)]
    cpu_ids = [0, 1, 2]
    pools.copy_out_staged(runs, cpu_ids)
    pools.gpu = jnp.zeros_like(pools.gpu)
    pools.copy_in_staged(cpu_ids, runs)
    k1, v1 = pools.read_tokens(block_ids, n_tokens)
    np.testing.assert_array_equal(k0, k1)
    np.testing.assert_array_equal(v0, v1)


def test_multi_turn_reuse_increments_round_trip():
    """The reuse mechanism swaps out INCREMENTS across turns (only tokens
    beyond the valid CPU prefix); after several increments a full staged
    swap-in must restore every block bit-exactly."""
    pools = _pools(nb=12, ncpu=12)
    snap = np.asarray(pools.gpu)
    gpu_ids = [3, 4, 5, 8, 9, 10]                   # two gpu runs
    cpu_ids = [0, 1, 2, 3, 4, 5]
    # turn 1: blocks 0..2 of the request; turn 2: blocks 3..4; turn 3: 5
    for lo, hi in ((0, 3), (3, 5), (5, 6)):
        runs = [(s, 1) for s in gpu_ids[lo:hi]]
        pools.copy_out_staged(runs, cpu_ids[lo:hi])
    pools.gpu = jnp.zeros_like(pools.gpu)
    pools.copy_in_staged(cpu_ids, [(3, 3), (8, 3)])
    got = np.asarray(pools.gpu)
    np.testing.assert_array_equal(got[:, :, gpu_ids], snap[:, :, gpu_ids])


def test_gather_scatter_bucketing_bounds_jit_cache():
    """Pow2 bucketing: a single-run swap growing from 1 to 20 blocks
    compiles O(log2) variants (not one per size), and repeating any shape
    compiles nothing new."""
    pools = _pools(nb=40, ncpu=64)
    g0, s0 = ops.swap_gather_cache_size(), ops.swap_scatter_cache_size()

    def sweep():
        for n in range(1, 21):
            pools.copy_out_staged([(0, n)], list(range(n)))
            pools.copy_in_staged(list(range(n)), [(0, n)])
    sweep()
    grown_g = ops.swap_gather_cache_size() - g0
    grown_s = ops.swap_scatter_cache_size() - s0
    assert grown_g <= 6, grown_g              # ceil(log2(20)) + 1
    assert grown_s <= 6, grown_s
    sweep()                                   # warm: zero new variants
    assert ops.swap_gather_cache_size() - g0 == grown_g
    assert ops.swap_scatter_cache_size() - s0 == grown_s


def test_copy_out_builds_no_program_for_a_new_block_count():
    """Once a slab bucket has run, a swap-out of a block count new to the
    process traces, lowers and builds nothing: the whole pow2 slab
    crosses to the host and is trimmed there (a slice on the device
    built one program per count, inside serving steps)."""
    from repro.core import tracing
    pools = _pools(nb=40, ncpu=64)
    snap = np.asarray(pools.gpu).view(np.uint16)
    for n in (1, 2, 3, 5, 9):                 # one count of each bucket
        pools.copy_out_staged([(0, n)], list(range(n)))
    tr = tracing.tracer()
    seen = {s.id for s in tr.spans()}
    for n in (4, 6, 7, 8, 10, 11, 12, 13, 14, 15, 16):
        pools.copy_out_staged([(1, n)], list(range(20, 20 + n)))
        np.testing.assert_array_equal(pools.cpu[:, :, 20:20 + n],
                                      snap[:, :, 1:1 + n])
    built = [s.name for s in tr.spans() if s.id not in seen
             and s.name.split(":")[0] in ("trace", "lower", "build")]
    assert built == []


def test_deferred_copy_out_has_read_the_pool_when_it_returns():
    """``deferred=True`` returns the transfer and the host store, to run
    without the pool lock: the pool may be overwritten (donated) before
    they run, and the host still receives the blocks as they were."""
    pools = _pools()
    snap = np.asarray(pools.gpu).view(np.uint16)
    runs = [(1, 3), (9, 2)]
    cpu_ids = [4, 0, 7, 12, 3]
    store = pools.copy_out_staged(runs, cpu_ids, deferred=True)
    assert not pools.cpu.any()          # nothing stored yet
    pools.copy_in_staged(cpu_ids, runs)  # zero blocks, pool donated
    assert not np.asarray(pools.gpu).view(np.uint16)[:, :, 1:4].any()
    store()
    np.testing.assert_array_equal(pools.cpu[:, :, cpu_ids],
                                  snap[:, :, [1, 2, 3, 9, 10]])
    assert pools.copy_out_staged(runs, cpu_ids) is None


def test_copy_in_double_buffered_bit_exact_multi_stage():
    """Double-buffered swap-in (bounded sub-slabs): splitting a staged
    copy-in mid-run must land every block bit-exactly, leak into no
    others, and keep the per-stage transfer accounting
    (``h2d_transfers == n_shards * staged_in_calls``)."""
    pools = _pools()
    snap = np.asarray(pools.gpu)
    runs = [(1, 3), (6, 2), (11, 2)]                # 7 blocks, 3 runs
    blocks = runs_to_indices(runs)
    cpu_ids = [5, 0, 9, 2, 17, 21, 3]
    pools.copy_out_staged(runs, cpu_ids)
    pools.gpu = jnp.zeros_like(pools.gpu)
    in0, h0 = pools.staged_in_calls, pools.h2d_transfers
    pools.copy_in_staged(cpu_ids, runs, stage_blocks=3)
    n_stages = len(split_runs(runs, 3))             # 3 — splits (1,3) off
    assert n_stages == 3
    assert pools.staged_in_calls - in0 == n_stages
    assert pools.h2d_transfers - h0 == pools.n_shards * n_stages
    got = np.asarray(pools.gpu)
    np.testing.assert_array_equal(got[:, :, blocks], snap[:, :, blocks])
    other = [b for b in range(16) if b not in blocks]
    assert not np.any(got[:, :, other]), "stage scatter leaked"


def test_copy_in_stage_split_matches_monolithic_slab():
    """stage_blocks=0 (one monolithic slab) and a multi-stage split of
    the SAME swap-in produce bit-identical pools."""
    p1, p2 = _pools(), _pools()
    runs = [(0, 4), (8, 4)]
    cpu_ids = list(range(8))
    for p in (p1, p2):
        p.copy_out_staged(runs, cpu_ids)
        p.gpu = jnp.zeros_like(p.gpu)
    in1, in2 = p1.staged_in_calls, p2.staged_in_calls
    p1.copy_in_staged(cpu_ids, runs, stage_blocks=0)
    p2.copy_in_staged(cpu_ids, runs, stage_blocks=3)
    assert p1.staged_in_calls - in1 == 1            # single shot
    assert p2.staged_in_calls - in2 == len(split_runs(runs, 3))
    np.testing.assert_array_equal(np.asarray(p1.gpu), np.asarray(p2.gpu))


def test_split_and_trim_runs():
    runs = [(0, 5), (10, 2), (20, 1)]
    assert split_runs(runs, 0) == [runs]
    assert split_runs([], 4) == []
    chunks = split_runs(runs, 3)
    assert chunks == [[(0, 3)], [(3, 2), (10, 1)], [(11, 1), (20, 1)]]
    assert runs_to_indices([r for c in chunks for r in c]) \
        == runs_to_indices(runs)
    assert trim_runs(runs, 6) == [(0, 5), (10, 1)]
    assert trim_runs(runs, 0) == []
    assert trim_runs(runs, 99) == runs


# ---------------------------------------------------------------------------
# engine-level: chunked dispatch, donation safety, batch-bucket admission
# ---------------------------------------------------------------------------


def _sim_engine(**kw):
    from repro.core import EngineConfig, FastSwitchEngine
    from repro.data.priority import PriorityTrace
    from repro.data.sharegpt import Conversation, Turn
    convs = [Conversation(conv_id=0, arrival_s=0.0, turns=[Turn(8, 20)],
                          think_time_s=0.1)]
    defaults = dict(mode="sim", num_gpu_blocks=64, num_cpu_blocks=256,
                    block_size=16)
    defaults.update(kw)
    cfg = EngineConfig(**defaults).with_policy("fastswitch")
    return FastSwitchEngine(cfg, convs,
                            trace=PriorityTrace("random", 1e-9, seed=0))


def test_chunked_swap_in_promotes_only_when_all_chunks_done():
    """A swap split into chunk tasks: the request must stay SWAPPING_IN
    until its LAST chunk completes (the old per-task promotion would have
    promoted on the first)."""
    from repro.core.scheduler import ReqState
    eng = _sim_engine(swap_chunk_blocks=1, num_gpu_blocks=512)
    eng.swap.adaptive = False                 # force async swaps
    eng.step()
    req = eng.sched.requests[0]
    # grow the context so the swap spans several 1-block chunks
    grow = 4 * 16 - req.context_tokens
    eng.gpu_mgr.allocate_tokens(0, grow)
    eng.gpu_mgr.note_tokens(0, grow)
    req.context_tokens += grow
    eng._preempt(0)
    assert eng._swap_in(0) is False
    tasks = [t for t in eng.swap.ongoing_swap_in if t.req_id == 0]
    assert len(tasks) >= 3, "swap was not split into chunk tasks"
    # advance to just past the FIRST chunk: must not be promoted yet
    eng.clock.advance_to(min(t.done_at for t in tasks) + 1.0)
    eng.swap.poll_completed(eng.clock)
    ongoing = {t.req_id for t in eng.swap.ongoing_swap_in}
    assert 0 in ongoing
    eng.step()
    assert req.state == ReqState.SWAPPING_IN, \
        "request promoted before all chunk tasks completed"
    eng.clock.advance_to(max(t.done_at for t in tasks) + 1.0)
    eng.step()
    # promoted once every chunk retired (the inflated context makes the
    # turn finish in the same iteration, so DONE also proves promotion)
    assert req.state in (ReqState.RUNNING, ReqState.DONE)


def test_conflict_sync_waits_only_overlapping_chunk():
    """Fine-grained chunk conflicts: resolving a conflict on one chunk's
    blocks must retire only that chunk, not the whole swap."""
    eng = _sim_engine(swap_chunk_blocks=1, num_gpu_blocks=512)
    eng.swap.adaptive = False
    eng.step()
    req = eng.sched.requests[0]
    grow = 4 * 16 - req.context_tokens
    eng.gpu_mgr.allocate_tokens(0, grow)
    eng.gpu_mgr.note_tokens(0, grow)
    req.context_tokens += grow
    eng._preempt(0)
    eng._swap_in(0)
    tasks = [t for t in eng.swap.ongoing_swap_in if t.req_id == 0]
    assert len(tasks) >= 3
    first = tasks[0]
    eng.swap.resolve_conflicts(eng.clock, list(first.gpu_blocks))
    remaining = [t for t in eng.swap.ongoing_swap_in if t.req_id == 0]
    assert first not in remaining
    assert len(remaining) == len(tasks) - 1, \
        "conflict sync retired more than the overlapping chunk"


def test_swap_in_dispatches_token_ordered_runs_on_fragmented_alloc():
    """A fragmented pool can satisfy a swap-in with groups whose physical
    starts DESCEND (block table [8..12, 0..2]).  The data plane pairs GPU
    runs positionally with the token-ordered CPU block list, so the runs
    must follow TOKEN order — ``request_runs``' physically-sorted spans
    would restore every block into the wrong block-table slot."""
    from repro.core.block_group import BlockGroup, _ReqState
    from repro.core.scheduler import ReqState
    eng = _sim_engine(num_gpu_blocks=64)
    eng.swap.adaptive = False
    eng.step()                              # admit rid 0
    req = eng.sched.requests[0]
    # hand-craft a descending-start allocation: tokens 0..79 -> blocks
    # 8..12, tokens 80..127 -> blocks 0..2
    eng.gpu_mgr.release_request(0)
    eng.gpu_mgr.requests[0] = _ReqState(groups=[
        BlockGroup(start=8, length=5, owner=0, used=5),
        BlockGroup(start=0, length=3, owner=0, used=3)])
    eng.gpu_mgr._token_counts[0] = 8 * 16
    assert eng.gpu_mgr.request_runs(0) == [(0, 3), (8, 5)]   # sorted (wrong)
    eng.gpu_mgr.allocate_tokens = lambda rid, n: []          # keep crafted
    eng.gpu_mgr.note_tokens = lambda rid, n: None            # state as-is
    req.context_tokens = 8 * 16
    eng.sched.move(0, ReqState.SWAPPED)
    captured = []
    orig = eng.swap.dispatch
    eng.swap.dispatch = lambda clock, rid, d, runs, *a, **k: \
        captured.append(list(runs)) or orig(clock, rid, d, runs, *a, **k)
    eng._swap_in(0)
    flat = [r for runs in captured for r in runs]
    assert flat == [(8, 5), (0, 3)], \
        f"swap-in runs not in token order: {flat}"


def test_admission_target_sim_mode_is_max_running():
    eng = _sim_engine(max_running=16)
    assert eng._admission_target() == 16


def test_desired_running_trims_bucket_spill():
    """Scheduler-side batch-bucket economics: a one-request spill past the
    compiled bucket is trimmed (admissions only), a half-bucket spill is
    kept, and running requests are never trimmed."""
    from repro.core.scheduler import PriorityScheduler, Request, ReqState
    from repro.data.sharegpt import Conversation, Turn

    class _Trace:
        def priority(self, rid):
            return -rid           # rid 0 = highest priority

    sched = PriorityScheduler(_Trace(), max_running=48)
    for i in range(5):
        req = Request(conv=Conversation(conv_id=i, arrival_s=0.0,
                                        turns=[Turn(8, 8)],
                                        think_time_s=0.1))
        req.begin_turn(0.0)
        sched.add_request(req)
    budget = 10_000
    # no bucket hint: all 5 chosen
    assert len(sched.desired_running(budget, 16)) == 5
    # bucket 4: spill of 1 (< half of the next bucket's rows) -> trimmed
    assert len(sched.desired_running(budget, 16, batch_bucket=4)) == 4
    # bucket 2: 5 = boundary 4 + spill 1 < 2 -> trimmed to 4
    assert len(sched.desired_running(budget, 16, batch_bucket=2)) == 4
    # a running request at the tail is never trimmed: the trim skips it
    # and removes the lowest-priority non-running entry instead
    sched.move(4, ReqState.RUNNING)
    chosen = sched.desired_running(budget, 16, batch_bucket=4)
    assert len(chosen) == 4 and 4 in chosen and 3 not in chosen


def test_swap_in_copy_ordered_behind_queued_swap_out_data():
    """A swap-in reads CPU blocks that a still-queued swap-out of the
    same request writes; worker execution is not FIFO, so the in-copy
    must await the out-task's data future (``copy_deps``) — without it,
    a slow out-copy lets the in-copy restore stale zeros."""
    import time as _time
    from repro.core.swap_manager import MultithreadingSwapManager, SimClock
    from repro.io.cost_model import TPU_V5E_HOST

    def run(with_deps):
        pools = _pools(nb=8, ncpu=8)
        snap = np.asarray(pools.gpu)
        mgr = MultithreadingSwapManager(TPU_V5E_HOST, pools)
        clock = SimClock()
        runs_out, cpu_ids = [(2, 2)], [0, 1]
        runs_in = [(5, 2)]                     # swap-in relocates the blocks
        # model the race window: the out-worker is descheduled between
        # picking up the task and acquiring the pool lock
        orig_run = mgr._run_copy_guarded

        def delayed_run(task, deps):
            _time.sleep(0.2)
            return orig_run(task, deps)
        mgr._run_copy_guarded = delayed_run
        out = mgr.dispatch(clock, 1, "out", runs_out, 1024,
                           runs_to_indices(runs_out), asynchronous=True,
                           copy_fn=lambda: pools.copy_out_staged(runs_out,
                                                                 cpu_ids),
                           cpu_blocks=cpu_ids)
        mgr._run_copy_guarded = orig_run
        deps = mgr.data_deps(cpu_ids)
        assert deps == [out.future]
        # overlap-keyed: disjoint CPU blocks have no dependency, and a
        # cross-request write to the SAME blocks (contamination handing a
        # victim's CPU blocks to a new owner) does
        assert mgr.data_deps([7]) == []
        assert mgr.data_deps([cpu_ids[0]]) == [out.future]
        mgr.dispatch(clock, 1, "in", runs_in, 1024, runs_to_indices(runs_in),
                     asynchronous=True,
                     copy_fn=lambda: pools.copy_in_staged(cpu_ids, runs_in),
                     copy_deps=deps if with_deps else (),
                     cpu_blocks=cpu_ids)
        mgr.shutdown()                         # join both workers
        got = np.asarray(pools.gpu)
        return np.array_equal(got[:, :, [5, 6]], snap[:, :, [2, 3]])

    assert not run(with_deps=False), \
        "race did not reproduce — the scenario no longer tests ordering"
    assert run(with_deps=True), \
        "swap-in copy ran before the queued swap-out wrote CPU"


# ---------------------------------------------------------------------------
# real mode: chunked staged swaps preserve tokens under storm preemption
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    cfg = get_smoke_config("qwen2-1.5b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return {"cfg": cfg, "params": params}


def test_real_chunked_storm_matches_unchunked(tiny_model):
    """swap_chunk_blocks=1 forces every storm swap through multi-chunk
    dispatch (chunk-granular conflict syncs, per-chunk pool-lock holds);
    the generated token streams must be identical to the unchunked run —
    and the engine must hold no stale pool reference across rebinds."""
    from repro.core import EngineConfig, FastSwitchEngine
    from repro.data.priority import PriorityTrace
    from repro.data.sharegpt import Conversation, Turn

    def run(chunk):
        convs = [Conversation(conv_id=i, arrival_s=0.0,
                              turns=[Turn(16, 20)], think_time_s=0.2)
                 for i in range(3)]
        cfg = EngineConfig(mode="real", num_gpu_blocks=8, num_cpu_blocks=512,
                           max_running=4, max_batch=4,
                           swap_chunk_blocks=chunk).with_policy("fastswitch")
        eng = FastSwitchEngine(
            cfg, convs, trace=PriorityTrace("random", 0.5, seed=11),
            model_bundle=tiny_model)
        eng.run(max_iterations=20_000)
        assert eng.done()
        return eng

    e1 = run(chunk=0)                      # unchunked
    e2 = run(chunk=1)                      # every block its own chunk task
    assert e2.metrics.preemptions > 0
    assert e1._token_hist_by_conv == e2._token_hist_by_conv
