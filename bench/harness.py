"""Run one cell once: set up, drive the window, check, report.

The client is the benchmark's own, single-threaded: it calls
``ServingEngine.add_request`` / ``continue_session`` / ``step`` in real
mode on one device and stamps every token with ``time.perf_counter`` at
the ``step`` return that delivered it.  No number of the engine's
simulated clock is used.

Set-up (``setup_s``, from the process's start to the window's start):
the weights made on the device from the seed, the engine, the decode
buckets grown once to the mix's caps, every prefill, insert and swap
shape the mix can reach run once, then the traffic itself for the mix's
``warmup_s``.  The window follows for ``--seconds``; the traffic keeps
its course across the boundary.
"""
from __future__ import annotations

import contextlib
import gc
import json
import sys
import time
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional, Tuple

from bench import correct, stats
from bench.model import make_params, program_config
from bench.spec import Cell, generator, metric_reader
from bench.traffic.base import pow2

OK_REASONS = ("length", "stop")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# programs built inside the window
# ---------------------------------------------------------------------------


class CompileWatch:
    """Counts the programs built (compiled, or loaded from the persistent
    cache) while ``active``, with their names."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.active = False
        self.counts = {"built": 0, "cache_hits": 0}
        self.names: List[str] = []

    def _duration(self, event, secs, fun_name="", **_):
        if self.active and event == self.BUILD:
            self.counts["built"] += 1
            self.names.append(str(fun_name))

    def _event(self, event, **_):
        if self.active and event == self.HIT:
            self.counts["cache_hits"] += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


# ---------------------------------------------------------------------------
# host spans (traced runs only)
# ---------------------------------------------------------------------------


@dataclass
class Spans:
    """The benchmark's spans around calls into the program's layers,
    kept in memory on the host clock and annotated into the profiler's
    trace as ``bench:<name>``."""
    records: List[Tuple[str, float, float]] = field(default_factory=list)
    decode_rows: List[Tuple[float, List[int]]] = field(default_factory=list)
    prefill_tokens: List[Tuple[float, int]] = field(default_factory=list)

    @contextlib.contextmanager
    def span(self, name: str):
        from jax.profiler import TraceAnnotation
        t0 = time.perf_counter()
        with TraceAnnotation("bench:" + name):
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def wrap(self, obj, attr: str, name: str,
             note: Optional[Callable] = None) -> None:
        fn = getattr(obj, attr)

        def wrapped(*a, **kw):
            if note is not None:
                note(*a, **kw)
            with self.span(name):
                return fn(*a, **kw)
        setattr(obj, attr, wrapped)

    def instrument(self, engine) -> None:
        """Spans around the engine step and each layer it calls into."""
        spans = self
        self.wrap(engine, "step", "step")
        self.wrap(engine, "_swap_out", "swap_out")
        self.wrap(engine, "_swap_in", "swap_in")
        self.wrap(engine, "_dispatch_swap", "swap_copy")
        self.wrap(engine.swap, "synchronize", "swap_sync")
        self.wrap(engine, "_real_prefill", "prefill")
        self.wrap(engine.runner, "flush", "flush_wait")
        self.wrap(engine.runner, "decode", "decode_dispatch",
                  note=lambda views, pool: spans.decode_rows.append(
                      (time.perf_counter(),
                       [len(v.token_history) for v in views])))
        self.wrap(engine.runner, "prefill_chunk_compute", "prefill_compute",
                  note=lambda rid, n: spans.prefill_tokens.append(
                      (time.perf_counter(), int(n))))


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------


def make_engine(cell: Cell, bundle: Dict, seed: int, device):
    from repro.core import EngineConfig, ServingEngine
    from repro.data.priority import PriorityTrace
    e = cell.mix["engine"]
    cfg = EngineConfig(mode="real", num_gpu_blocks=e["num_gpu_blocks"],
                       num_cpu_blocks=e["num_cpu_blocks"],
                       block_size=e["block_size"],
                       max_running=e["max_running"],
                       max_batch=e["max_running"],
                       seed=seed % (2 ** 31)).with_policy(e["policy"])
    # the priorities are the traffic's, like its sizes and arrivals: the
    # same for every run seed
    p = e["priority"]
    trace = PriorityTrace(p["pattern"], p["update_freq"],
                          seed=cell.mix["shape_seed"],
                          stickiness=p["stickiness"])
    return ServingEngine(cfg, trace=trace, model_bundle=bundle,
                         stream_tokens=True, device=device)


def grow_buckets(engine, cell: Cell) -> Tuple[int, int]:
    """Grow the decode buckets once to the mix's caps (batch: the running
    cap; pages: the longest context), so one decode variant serves the
    whole run."""
    e = cell.mix["engine"]
    batch = pow2(e["max_running"])
    pages = pow2(-(-cell.mix["max_context"] // e["block_size"]))
    engine.runner._rebuild([], pages, batch)
    return batch, pages


def _swap_shapes(max_blocks: int):
    """One list of run lengths for each program the staged swap copies
    bucket to (pow2 run count, pow2 slab, pow2 longest run), over chunks
    of at most ``max_blocks`` blocks."""
    out = {}
    for n in range(1, max_blocks + 1):
        for total in range(n, max_blocks + 1):
            for longest in range(-(-total // n), total - n + 2):
                key = (pow2(n), pow2(total), pow2(longest))
                if key in out:
                    continue
                lens, rest = [longest], total - longest
                for i in range(n - 1):
                    take = min(longest, rest - (n - 2 - i))
                    lens.append(take)
                    rest -= take
                out[key] = lens
    return list(out.values())


def warm_shapes(engine, cell: Cell, params) -> int:
    """Run once every program the mix can reach that a short warm-up
    could miss: each prefill (chunk, carry) bucket pair with its insert,
    the carry seed and growth of a reused prefix, the staged swap copies
    of every bucket, and the per-row state writes of the decode tables.
    Returns the number of calls made."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops
    cfg = engine.model_bundle["cfg"]
    bs = engine.config.block_size
    trash = engine.config.num_gpu_blocks - 1
    pools = engine.pools
    gen = generator(cell.mix)
    calls = 0
    for chunk, prefix, seeded in gen.prefill_shapes(cell.mix, bs):
        k = v = None
        if seeded:
            k, v = ops.seed_prefill_carry(pools.gpu, [trash] * (seeded // bs),
                                          seeded, trash=trash)
        out = ops.prefill_chunk(params, [1] * chunk, k, v, prefix, cfg=cfg,
                                block_size=bs)
        pools.gpu = ops.insert_prefill(pools.gpu, out[3], out[4],
                                       [trash] * (out[3].shape[1] // bs), bs)
        jax.block_until_ready(pools.gpu)
        calls += 1
    if gen.swaps(cell.mix):
        n_cpu = engine.config.num_cpu_blocks
        # a chunk never holds more blocks than the pool (runs plus gaps)
        most = min(engine.config.swap_chunk_blocks,
                   pow2((trash + 2) // 2) // 2)
        for lens in _swap_shapes(most):
            runs, start = [], 0
            for n in lens:
                runs.append((start, n))
                start += n + 1
            cpu = list(range(n_cpu - sum(lens), n_cpu))
            pools.copy_out_staged(runs, cpu)
            pools.copy_in_staged(cpu, runs)
            calls += 2
        jax.block_until_ready(pools.gpu)
    # per-row writes of the persistent decode tables, for every count of
    # rows that can change in one step, made as the runner makes them: on
    # tables placed on the device, with rows and values from the host
    r = engine.runner
    B, P = r._bt.shape
    arrays = jax.device_put(
        [np.zeros((B, P), np.int32), np.zeros((B,), np.int32),
         np.zeros((B,), np.int32), np.zeros((B, 2), np.uint32),
         np.zeros((B, 3), np.float32)], engine.device)
    for n in range(1, B + 1):
        rows = jnp.asarray(list(range(n)), jnp.int32)
        for a in arrays:
            a.at[rows].set(jnp.asarray(np.zeros((n,) + a.shape[1:],
                                                a.dtype)))
            calls += 1
    jax.block_until_ready(arrays)
    return calls


# ---------------------------------------------------------------------------
# the client
# ---------------------------------------------------------------------------


class Client:
    """Sends the generator's turns when due and stamps their tokens."""

    def __init__(self, engine, traffic, t0: float, spans: Optional[Spans]):
        from repro.core import SamplingParams
        self.engine, self.traffic, self.t0 = engine, traffic, t0
        self.spans = spans
        self._sp = SamplingParams
        self.turns: Dict[Tuple[int, int], Dict] = {}
        self.current: Dict[int, Tuple[int, int]] = {}
        self.backlog: List[Tuple[float, int]] = []
        self.late: List[Tuple[float, float]] = []      # (due, late_s)
        self.errors: List[str] = []

    def _submit(self):
        now = time.perf_counter()
        for s in self.traffic.pop_due(now - self.t0):
            sp = self._sp(max_tokens=s.max_tokens, temperature=0.0)
            if self.traffic.is_first(s.sid, s.turn):
                self.engine.add_request(s.prompt, sp, handle=s.sid,
                                        retain_kv=s.retain)
            else:
                self.engine.continue_session(s.sid, s.prompt, sp,
                                             retain_kv=s.retain)
            sent = time.perf_counter()
            due = self.t0 + s.due
            self.late.append((due, sent - due))
            key = (s.sid, s.turn)
            self.current[s.sid] = key
            self.turns[key] = {"due": due, "sent": sent, "times": [],
                               "tokens": [], "prompt": s.prompt,
                               "done": False, "end": None, "reason": None,
                               "max_tokens": s.max_tokens}

    def _collect(self, outs):
        now = time.perf_counter()
        for o in outs:
            key = self.current[o.handle]
            t = self.turns[key]
            if o.token_ids:
                t["tokens"].extend(o.token_ids)
                t["times"].extend([now] * len(o.token_ids))
            if o.finished:
                t["done"], t["end"], t["reason"] = True, now, o.finish_reason
                if o.finish_reason in OK_REASONS:
                    self.traffic.finished(key[0], key[1], now - self.t0)
                else:
                    self.errors.append(f"{key}: {o.finish_reason} {o.error}")

    def run_until(self, t_end: float, sample_from: Optional[float] = None):
        next_sample = sample_from
        while True:
            now = time.perf_counter()
            if now >= t_end:
                return
            self._submit()
            if next_sample is not None and now >= next_sample:
                self.backlog.append((now, sum(
                    1 for t in self.turns.values() if not t["times"]
                    and not t["done"])))
                next_sample += 1.0
            if self.engine.has_work():
                self._collect(self.engine.step())
                continue
            wait = min(self.t0 + self.traffic.next_due(), t_end) - now
            if wait > 0:
                if self.spans is not None:
                    with self.spans.span("client_idle"):
                        time.sleep(wait)
                else:
                    time.sleep(wait)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def end_to_end(cell: Cell, turns: List[Dict], w0: float, w1: float,
               setup_s: float) -> Dict[str, Dict]:
    ttft = stats.ttft_samples(turns, w0, w1)
    tbt = stats.tbt_samples(turns, w0, w1)
    vals = {
        "ttft_p90_ms": (stats.percentile(ttft, 90), "ms", 1e3),
        "tbt_p99_ms": (stats.percentile(tbt, 99), "ms", 1e3),
        "tbt_mean_ms": (sum(tbt) / len(tbt) if tbt else None, "ms", 1e3),
        "output_tok_s": (stats.tokens_in(turns, w0, w1) / (w1 - w0),
                         "tokens/s", 1.0),
        "setup_s": (setup_s, "s", 1.0),
    }
    out = {}
    for m in cell.end_to_end:
        v, unit, scale = vals[m["name"]]
        if v is not None:
            out[m["name"]] = {"value": v * scale, "unit": m["unit"]}
    return out


def _counters(engine) -> Dict[str, float]:
    m, sw = engine.metrics, engine.swap.stats()
    mid = sum(1 for e in (engine.events or [])
              if e.kind == "preempt")
    return {"preemptions": m.preemptions, "preempt_events": mid,
            "swap_out": m.swap_out_count, "swap_in": m.swap_in_count,
            "blocks_out": sw["blocks_out"], "blocks_in": sw["blocks_in"],
            "steps": m.iterations, "faulted": m.faulted,
            "copy_failures": sw["copy_failures"],
            "swap_failure_resumes": m.swap_failure_resumes,
            "prefill_tokens": engine.runner.stats.prefill_tokens}


def _device_info(devices, chips: int, peak: Optional[int]) -> Dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": chips, "memory_peak_bytes": peak}


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        proc_start: float, trace_dir=None, devices=None) -> Dict:
    """One run of ``cell``; returns the result line's object."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = devices or jax.devices()
    dev = devices[0]
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"compile cache {cache}")
    watch = CompileWatch()
    with watch:
        return _run(cell, seed, seconds, trace, proc_start, trace_dir,
                    devices, dev, watch)


def _run(cell, seed, seconds, trace, proc_start, trace_dir, devices, dev,
         watch) -> Dict:
    import jax
    hp = cell.config
    t = time.perf_counter()
    params = make_params(hp, seed, dev)
    jax.block_until_ready(params)
    log(f"params: {sum(x.nbytes for x in jax.tree.leaves(params))} bytes "
        f"in {time.perf_counter() - t:.3f} s")
    bundle = {"cfg": program_config(hp), "params": params}
    engine = make_engine(cell, bundle, seed, dev)
    batch, pages = grow_buckets(engine, cell)
    t = time.perf_counter()
    n_warm = warm_shapes(engine, cell, params)
    log(f"decode buckets batch {batch} x pages {pages}; warmed {n_warm} "
        f"shapes in {time.perf_counter() - t:.3f} s")

    spans = Spans() if trace else None
    if spans is not None:
        spans.instrument(engine)
    traffic = generator(cell.mix).build(cell.mix, seed, hp["vocab_size"])
    t0 = time.perf_counter()
    client = Client(engine, traffic, t0, spans)
    w0 = t0 + cell.mix["warmup_s"]
    client.run_until(w0)
    jax.block_until_ready(engine.pools.gpu)
    w0 = time.perf_counter()
    w1 = w0 + seconds
    setup_s = w0 - proc_start
    before = _counters(engine)
    watch.active = True
    trace_span = None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        trace_end = w0 + min(seconds, cell.mix.get("trace_s", seconds))
        with spans.span("window"):
            a = time.perf_counter()
            client.run_until(trace_end, sample_from=w0)
            jax.block_until_ready(engine.pools.gpu)
            trace_span = (a, time.perf_counter())
        jax.profiler.stop_trace()
        client.run_until(w1)
    else:
        client.run_until(w1, sample_from=w0)
    watch.active = False
    after = _counters(engine)
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    delta = {k: after[k] - before[k] for k in after}
    turns = list(client.turns.values())
    e2e = end_to_end(cell, turns, w0, w1, setup_s)

    late = [s for d, s in client.late if w0 <= d < w1]
    log(f"window: {seconds} s; turns due {sum(1 for t in turns if w0 <= t['due'] < w1)}"
        f", TTFT samples {len(stats.ttft_samples(turns, w0, w1))}, TBT "
        f"samples {len(stats.tbt_samples(turns, w0, w1))}, tokens "
        f"{stats.tokens_in(turns, w0, w1)}")
    fin = [t["end"] - t["due"] for t in turns
           if t["done"] and w0 <= t["end"] < w1]
    log(f"turns finished in window {len(fin)}, mean due to last token "
        f"{sum(fin) / max(1, len(fin)):.3f} s")
    log("generator lateness in window: " + json.dumps({
        "sends": len(late), "max_s": max(late, default=0.0),
        "mean_s": (sum(late) / len(late)) if late else 0.0}))
    log("programs built inside the window: " + json.dumps(
        dict(watch.counts, names=sorted(set(watch.names))[:20])))
    log("engine counters in window: " + json.dumps(delta))
    if client.backlog:
        ts = [b[0] - w0 for b in client.backlog]
        ys = [b[1] for b in client.backlog]
        log(f"backlog (turns sent, no first token yet): start {ys[0]}, end "
            f"{ys[-1]}, max {max(ys)}, slope "
            f"{stats.slope(ts, ys):.4f} turns/s")
    kv_cap = (engine.config.num_gpu_blocks - 1) * engine.config.block_size
    log(f"pool: {kv_cap} tokens; peak memory {peak} bytes")

    # sessions whose KV came back by swap-in: a follow-up turn admitted
    # on its retained KV, or a turn swapped out mid-decode and back in
    swapped_in = {e.handle for e in (engine.events or [])
                  if e.kind == "swap_in"
                  or (e.kind == "admit" and e.data.get("reused", 0) > 0)}
    midturn = swapped_in & {e.handle for e in (engine.events or [])
                            if e.kind == "preempt"
                            and e.data.get("to") == "swapped"}
    engine.shutdown()
    failed = sum(1 for t in turns if w0 <= t["due"] < w1 and t["done"]
                 and t["reason"] not in OK_REASONS)
    attempted = sum(1 for t in turns if w0 <= t["due"] < w1)

    # per-layer metrics read the trace before the program's state goes
    metrics = dict(e2e)
    breakdown = ctx = None
    busy_s = window_s = None
    if trace:
        from bench import trace as tr
        red = tr.load(trace_dir)
        lo, hi = red.window()
        window_s = (hi - lo) / 1e9
        busy_s = sum(red.busy_ns(d, lo, hi) for d in red.ops) / max(
            1, len(red.ops)) / 1e9
        ctx = SimpleNamespace(
            hp=hp, peaks=cell.peaks, trace=red, lo=lo, hi=hi,
            spans=spans, host_window=trace_span, window=(w0, w1),
            counters=delta, block_size=engine.config.block_size)
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        from bench.breakdown import breakdown as bd
        breakdown = bd(red, lo, hi)

    # correctness: after the window, with the program's state freed (the
    # spans' wrappers hold the engine too)
    turns_by_key = client.turns
    del engine, client, spans, ctx
    gc.collect()
    sessions = correct.sequences(turns_by_key, swapped_in, midturn)
    verdict = correct.check(cell, params, sessions, seed)
    result = {"correct": verdict["correct"], "attempted": attempted,
              "failed": failed, "metrics": metrics,
              "device": _device_info(devices, cell.chips, peak)}
    if trace:
        result["device"].update(busy_s=busy_s, window_s=window_s)
        result["breakdown"] = breakdown
    result["compared"] = verdict["compared"]
    return result
