"""The in-program tracer (``repro.core.tracing``): span and mark records,
program build phases, the traced pool lock, and the spans a real-mode
``ServingEngine`` emits through a preemption, swap-out and swap-in."""
import sys
import threading
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from repro.core import tracing
from repro.core.swap_manager import MultithreadingSwapManager, SimClock
from repro.io.cost_model import TPU_V5E_HOST

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def fresh(monkeypatch):
    """A fresh process tracer for one test (program builds are not fed
    to it: those listeners belong to the first tracer)."""
    tr = tracing.Tracer()
    monkeypatch.setattr(tracing, "_TRACER", tr)
    return tr


def _union(intervals):
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def test_nested_spans_link_parents_and_self_time(fresh):
    with fresh.span("step", engine=3) as step:
        time.sleep(0.002)
        with fresh.span("swap.out", rid=7) as child:
            with fresh.span("swap.dispatch", rid=7) as grand:
                time.sleep(0.003)
        with fresh.span("decode.launch") as other:
            time.sleep(0.002)
    assert step.parent is None
    assert child.parent == step.id and other.parent == step.id
    assert grand.parent == child.id
    # the engine index is inherited from the parent; rid is the span's own
    assert {s.engine for s in (step, child, grand, other)} == {3}
    assert (step.rid, child.rid, grand.rid, other.rid) == (None, 7, 7, None)
    assert {s.thread for s in fresh.spans()} == {threading.get_ident()}
    # records land at exit: children first
    assert [s.name for s in fresh.spans()] == [
        "swap.dispatch", "swap.out", "decode.launch", "step"]
    inner = [s for s in fresh.spans() if s.parent == step.id]
    self_s = (step.t1 - step.t0) - _union((s.t0, s.t1) for s in inner)
    assert 0.0015 < self_s < (step.t1 - step.t0) - 0.004


def test_marks_carry_rid_engine_and_data(fresh):
    fresh.mark("arrive", 4, engine=2, prompt_tokens=10)
    with fresh.span("step", engine=5):
        fresh.mark("admit", 4, reused=0)
    a, b = fresh.marks()
    assert (a.name, a.rid, a.engine, a.data) == ("arrive", 4, 2,
                                                  {"prompt_tokens": 10})
    assert (b.name, b.rid, b.engine, b.data) == ("admit", 4, 5,
                                                  {"reused": 0})
    assert a.t <= b.t


def test_the_ring_stays_bounded():
    tr = tracing.Tracer()
    n = tracing.CAPACITY // 2 + 4
    for i in range(n):
        with tr.span("s", rid=i):
            pass
        tr.mark("m", i)
    spans, marks = tr.spans(), tr.marks()
    assert len(spans) + len(marks) == tracing.CAPACITY
    # the oldest fell out: records of the first four turns are gone
    assert min(r.rid for r in spans + marks) == 4
    assert spans[-1].rid == marks[-1].rid == n - 1


def test_a_build_phase_is_a_finished_span_ending_now(fresh):
    t = time.perf_counter()
    with fresh.span("step", engine=2) as outer:
        fresh._build("/jax/core/compile/backend_compile_duration", 0.25,
                     fun_name="jit(f)")
        fresh._build("/jax/compilation_cache/cache_retrieval_time_sec",
                     0.1)
    (b,) = [s for s in fresh.spans() if s.name != "step"]
    assert (b.name, b.parent, b.engine, b.rid) == ("build:jit(f)", outer.id,
                                                    2, None)
    assert b.thread == outer.thread
    assert b.t1 - b.t0 == pytest.approx(0.25)
    assert t <= b.t1 <= outer.t1
    # outside any span a build has no parent and no engine
    fresh._build("/jax/core/compile/jaxpr_trace_duration", 0.01,
                 fun_name="g")
    last = fresh.spans()[-1]
    assert (last.name, last.parent, last.engine) == ("trace:g", None, None)


def test_a_fresh_jit_build_is_spanned_under_the_open_span():
    tr = tracing.tracer()

    def tripled_plus_one(x):
        return x * 3 + 1

    with tr.span("step", engine=-5) as outer:
        jax.block_until_ready(jax.jit(tripled_plus_one)(jnp.ones((3, 17))))
    under = [s for s in tr.spans() if s.parent == outer.id]
    names = {s.name.split(":")[0] for s in under
             if "tripled_plus_one" in s.name}
    # traced to a jaxpr, lowered to MLIR, then compiled
    assert names == {"trace", "lower", "build"}
    builds = [s for s in under if s.name == "build:jit(tripled_plus_one)"]
    assert len(builds) == 1
    for s in under:
        assert s.engine == -5 and outer.t0 <= s.t0 <= s.t1 <= outer.t1


def test_the_traced_lock_records_waits_not_holds(fresh):
    lock = tracing.TracedLock("pool_lock")
    with lock:
        pass
    assert fresh.spans() == []            # no wait, no record
    held, release = threading.Event(), threading.Event()

    def holder():
        with lock:
            held.set()
            release.wait(5)

    th = threading.Thread(target=holder)
    th.start()
    assert held.wait(5)
    threading.Timer(0.05, release.set).start()
    with fresh.span("decode.launch") as outer:
        with lock:
            pass
    th.join(5)
    assert not th.is_alive()
    (wait,) = [s for s in fresh.spans() if s.name == "pool_lock.wait"]
    assert wait.parent == outer.id
    assert wait.t1 - wait.t0 > 0.03


def test_a_worker_thread_swap_copy_is_parented_on_its_own_thread(fresh):
    pools = SimpleNamespace(n_shards=1, with_data=True)
    sm = MultithreadingSwapManager(TPU_V5E_HOST, pools, trace_engine=9)
    ran = []
    try:
        with fresh.span("swap.out", rid=11, engine=9):
            task = sm.dispatch(SimClock(), 11, "out", [(0, 2)], 1024,
                               [0, 1], asynchronous=True,
                               copy_fn=lambda: ran.append(
                                   threading.get_ident()),
                               cpu_blocks=[0, 1])
            task.future.result(timeout=5)
    finally:
        sm.shutdown()
    (copy,) = [s for s in fresh.spans() if s.name == "swap.copy"]
    assert ran == [copy.thread] and copy.thread != threading.get_ident()
    assert copy.parent is None and copy.rid == 11 and copy.engine == 9


def test_a_conflict_sync_is_spanned_only_when_it_waits(fresh):
    sm = MultithreadingSwapManager(TPU_V5E_HOST, None)
    clock = SimClock()
    sm.dispatch(clock, 1, "in", [(4, 2)], 1024, [4, 5], asynchronous=True)
    assert sm.resolve_conflicts(clock, [0, 1]) == 0
    assert fresh.spans() == []
    assert sm.resolve_conflicts(clock, [5]) == 1
    names = [s.name for s in fresh.spans()]
    assert names == ["swap.sync", "swap.conflict"]
    sync, conflict = fresh.spans()
    assert sync.parent == conflict.id


# ---------------------------------------------------------------------------
# a real-mode engine on the tiny CPU configuration
# ---------------------------------------------------------------------------

ENGINE_SPANS = {"step", "step.rebalance", "step.admit", "step.alloc",
                "step.collect", "swap.out", "swap.in", "swap.dispatch",
                "swap.sync", "swap.conflict", "swap.copy", "pool_lock.wait",
                "prefill.compute", "prefill.insert", "prefill.first_token",
                "decode.rows", "decode.launch", "decode.flush"}
RID_SPANS = {"swap.out", "swap.in", "swap.dispatch", "swap.copy",
             "prefill.compute", "prefill.insert", "prefill.first_token"}
# what bench/harness.py's Spans.instrument wraps, by instance attribute
BENCH_HOOKS = {"": ("step", "_swap_out", "_swap_in", "_dispatch_swap",
                    "_real_prefill"),
               "runner": ("flush", "decode", "prefill_chunk_compute"),
               "swap": ("synchronize",)}


@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    cfg = get_smoke_config("qwen2-1.5b")
    return {"cfg": cfg, "params": T.init_params(cfg, jax.random.PRNGKey(0))}


def _engine(model, policy):
    from repro.core import EngineConfig, ServingEngine
    from repro.data.priority import PriorityTrace
    cfg = EngineConfig(mode="real", num_gpu_blocks=6, num_cpu_blocks=64,
                       max_running=4, max_batch=4,
                       block_size=16).with_policy(policy)
    return ServingEngine(cfg, trace=PriorityTrace("random", 0.5, seed=13),
                         model_bundle=model, stream_tokens=True)


def _serve_two_sessions(eng, hold_lock_at=None):
    """Two retained sessions over a pool too small for both, then a
    follow-up turn on the first; optionally another thread holds the
    pool lock through one step."""
    from repro.core import SamplingParams
    sp = SamplingParams(max_tokens=20, temperature=0.0)
    for h in range(2):
        eng.add_request(list(range(3 + h, 17 + h)), sp, handle=h,
                        retain_kv=True)
    steps = 0
    while eng.has_work():
        if steps == hold_lock_at:
            held = threading.Event()
            th = threading.Thread(target=_hold, args=(eng.swap._pool_lock,
                                                      held))
            th.start()
            assert held.wait(5)
            eng.step()
            th.join(5)
            assert not th.is_alive()
        else:
            eng.step()
        steps += 1
    eng.continue_session(0, [5, 6, 7],
                         SamplingParams(max_tokens=4, temperature=0.0))
    while eng.has_work():
        eng.step()
    eng.shutdown()


def _hold(lock, held):
    with lock:
        held.set()
        time.sleep(0.05)


def test_engine_emits_every_span_and_mark_with_its_rid(tiny_model):
    sys.path.insert(0, str(REPO))
    from bench.harness import Spans
    eng = _engine(tiny_model, "fastswitch")
    bench_spans = Spans()
    bench_spans.instrument(eng)
    _serve_two_sessions(eng, hold_lock_at=3)
    tr = tracing.tracer()
    spans = [s for s in tr.spans() if s.engine == eng.trace_engine]
    marks = [m for m in tr.marks() if m.engine == eng.trace_engine]
    names = {s.name for s in spans}
    assert ENGINE_SPANS <= names, ENGINE_SPANS - names
    assert eng.metrics.preemptions > 0 and eng.metrics.swap_in_count > 0
    for s in spans:
        if s.name in RID_SPANS:
            assert s.rid in (0, 1), s
    kinds = {m.name for m in marks}
    assert {"arrive", "continue", "admit", "swap_in", "first_token",
            "preempt", "finish"} <= kinds
    # each request mark comes from the engine's event stream, one for one
    assert [(m.name, m.rid) for m in marks if m.name != "paged_attn.pages"] \
        == [(e.kind, e.handle) for e in eng.events]
    # a swap-in mark lies inside the swap.in span of the same request
    for m in marks:
        if m.name == "swap_in":
            assert any(s.name == "swap.in" and s.rid == m.rid
                       and s.t0 <= m.t <= s.t1 for s in spans)
        if m.name == "first_token":
            assert any(s.name == "prefill.first_token" and s.rid == m.rid
                       and s.t1 <= m.t for s in spans)
    # the phases sit under the step; the lock wait under an engine span
    by_id = {s.id: s for s in spans}
    for s in spans:
        if s.name.startswith("step."):
            assert by_id[s.parent].name == "step"
    assert any(s.name == "pool_lock.wait" and s.parent in by_id
               and s.thread == by_id[s.parent].thread for s in spans)
    # the step spans are the engine's wall time
    steps = [s for s in spans if s.name == "step"]
    assert eng.metrics.callstack_wall_s == pytest.approx(
        sum(s.t1 - s.t0 for s in steps))
    assert eng.metrics.iterations == len(steps)
    # the benchmark's wrappers still see every call they wrap
    seen = {n for n, _, _ in bench_spans.records}
    assert {"step", "swap_out", "swap_in", "swap_copy", "swap_sync",
            "prefill", "flush_wait", "decode_dispatch",
            "prefill_compute"} <= seen


def test_each_decode_launch_marks_live_and_bucket_pages(tiny_model):
    """``paged_attn.pages``: ``live`` is the rows' block counts plus one
    trash page per padding row, ``bucket`` the batch bucket times the
    pages bucket.  The kernel reads each row's ``cdiv(ctx + 1, bs)``
    pages: the block count, or one fewer where the row's next page is
    already allocated."""
    eng = _engine(tiny_model, "fastswitch")
    runner = eng.runner
    launches = []
    decode = runner.decode

    def record(views, pool):
        out = decode(views, pool)
        pad = runner.batch_bucket - len(views)
        # the host mirror of a row's context, counted after the launch,
        # is the context the kernel attended (the new token included)
        read = [-(-runner._row_ctx[runner._rows[v.rid]] // runner.bs)
                for v in views]
        blocks = [len(v.block_ids) for v in views]
        assert all(0 <= n - r <= 1 for n, r in zip(blocks, read))
        launches.append((sum(blocks) + pad,
                         runner.batch_bucket * runner._pages_bucket))
        return out

    runner.decode = record
    _serve_two_sessions(eng)
    marks = [m for m in tracing.tracer().marks()
             if m.engine == eng.trace_engine
             and m.name == "paged_attn.pages"]
    assert len(marks) == len(launches) == runner.stats.steps > 0
    assert [(m.data["live"], m.data["bucket"]) for m in marks] == launches
    assert any(live < bucket for live, bucket in launches)


def test_a_recompute_resume_is_marked(tiny_model):
    eng = _engine(tiny_model, "vllm-recompute")
    _serve_two_sessions(eng)
    marks = [m for m in tracing.tracer().marks()
             if m.engine == eng.trace_engine]
    resumes = [m for m in marks if m.name == "resume"]
    assert resumes and {m.rid for m in resumes} <= {0, 1}
    # each resume follows a preemption of the same request
    for r in resumes:
        assert any(m.name == "preempt" and m.rid == r.rid and m.t < r.t
                   for m in marks)


def test_the_benchmark_hooks_still_exist(tiny_model):
    eng = _engine(tiny_model, "fastswitch")
    try:
        for owner, names in BENCH_HOOKS.items():
            obj = getattr(eng, owner) if owner else eng
            for name in names:
                assert callable(getattr(obj, name)), (owner, name)
    finally:
        eng.shutdown()


def test_device_programs_carry_the_scope_names(tiny_model):
    """Each traced body names its parts (``jax.named_scope``): the names
    ride every op's metadata into a profiler trace.  The jitted programs
    and kernels keep their own names, which the benchmark matches."""
    from repro.kernels import ops
    from repro.models.paged import paged_decode_step_device, prefill_kv_chunk
    cfg, params = tiny_model["cfg"], tiny_model["params"]
    L, H, D = cfg.n_layers, cfg.n_kv_heads, cfg.resolved_head_dim
    pool = jnp.zeros((L, 2, 8, 16, H, D), jnp.bfloat16)
    carry = jnp.zeros((L, 32, H, D), jnp.bfloat16)
    runs = jnp.zeros((2,), jnp.int32)
    i32 = jnp.zeros((2,), jnp.int32)

    def text(lowered):
        return lowered.as_text(debug_info=True)

    decode = text(paged_decode_step_device.lower(
        params, pool, jnp.zeros((2, 4), jnp.int32), i32, i32,
        jnp.ones((2,), bool), jnp.zeros((2, 2), jnp.uint32),
        jnp.zeros((2, 3), jnp.float32), cfg=cfg))
    prefill = text(prefill_kv_chunk.lower(
        params, jnp.zeros((1, 16), jnp.int32), carry, carry, jnp.int32(0),
        jnp.int32(16), cfg=cfg))
    progs = {
        "decode": decode, "prefill": prefill,
        "insert": text(ops._insert_prefill.lower(
            pool, carry, carry, runs, block_size=16)),
        "seed": text(ops._seed_carry.lower(pool, runs)),
        "grow": text(ops._grow_carry.lower(carry, n_new=64)),
        "gather": text(ops._gather_swap.lower(
            pool, runs, runs, runs, n_slab=2, run_blocks=1,
            interpret=True)),
        "scatter": text(ops._scatter_swap.lower(
            pool, jnp.zeros((2 * L, 2, 16, H, D), jnp.bfloat16), runs,
            runs, runs, run_blocks=1, interpret=True)),
    }
    for prog, scope in [("decode", "decode_layers"), ("decode", "paged_attn"),
                        ("decode", "sampling"), ("decode", "unembed"),
                        ("prefill", "prefill_forward"), ("prefill", "unembed"),
                        ("insert", "prefill_insert"), ("seed", "carry_seed"),
                        ("grow", "carry_grow"), ("gather", "swap_gather"),
                        ("scatter", "swap_scatter")]:
        assert f"{scope}/" in progs[prog], (prog, scope)
    assert "jit(paged_decode_step_device)" in decode
    assert "paged_attention" in decode
