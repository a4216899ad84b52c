"""DecodeRunner: shape bucketing, incremental block-table updates,
pool-donation safety across swap round-trips, deferred token sync."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache.paged import PagedPools, PoolSpec
from repro.configs import get_smoke_config
from repro.core.decode_runner import (DecodeRequestView, DecodeRunner,
                                      next_pow2)
from repro.kernels.ops import paged_attention
from repro.kernels.ref import paged_attention_ref
from repro.models import transformer as T
from repro.models.paged import paged_decode_step

BS = 4                       # tiny pages so boundaries come fast


@pytest.fixture(scope="module")
def model():
    cfg = get_smoke_config("qwen2-1.5b")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _mk_pool(cfg, nb):
    return jnp.zeros((cfg.n_layers, 2, nb, BS, cfg.n_kv_heads,
                      cfg.resolved_head_dim), jnp.bfloat16)


def test_next_pow2():
    assert [next_pow2(n) for n in (0, 1, 2, 3, 4, 5, 8, 9)] == \
        [1, 1, 2, 4, 4, 8, 8, 16]


def test_bucket_growth_matches_exact_shapes(model):
    """A context growing across page AND bucket edges must produce the
    same tokens as the legacy exact-width path, with O(log) compiles."""
    cfg, params = model
    nb = 8
    n_steps = 22                       # pages 1..6 -> buckets 1,2,4,8

    # legacy: exact-width block tables, synchronous token pull
    pool = _mk_pool(cfg, nb)
    hist_ref = [7]
    for ctx in range(n_steps):
        bt = jnp.asarray([list(range(ctx // BS + 1))], jnp.int32)
        nxt, _, pool = paged_decode_step(
            params, pool, bt, jnp.asarray([ctx], jnp.int32),
            jnp.asarray([hist_ref[-1]], jnp.int32), cfg=cfg)
        hist_ref.append(int(nxt[0]))

    # runner: bucketed persistent device state, deferred sync
    pool = _mk_pool(cfg, nb)
    runner = DecodeRunner({"cfg": cfg, "params": params},
                          block_size=BS, trash_block=nb - 1)
    c0 = DecodeRunner.jit_cache_size()
    hist = [7]
    for ctx in range(n_steps):
        blocks = list(range(ctx // BS + 1))
        pool = runner.decode([DecodeRequestView(0, blocks, hist)], pool)
    runner.flush()
    assert hist == hist_ref
    max_pages = (n_steps - 1) // BS + 1
    bound = math.ceil(math.log2(max_pages)) + 1
    compiles = DecodeRunner.jit_cache_size() - c0
    assert compiles <= bound, (compiles, bound)
    assert runner.stats.rebuilds == compiles
    # steady state: only the rows whose block lists changed were uploaded
    assert runner.stats.rows_updated < n_steps


def test_multi_request_join_leave_matches_legacy(model):
    """Requests joining, leaving (preemption) and rejoining through the
    incremental row machinery must match the rebuild-everything path."""
    cfg, params = model
    nb = 16

    def blocks_of(base, ctx):
        return [base + i for i in range(ctx // BS + 1)]

    # schedule: rid -> (join_step, leave_step, rejoin_step)
    plan = {0: (0, None, None), 1: (0, 6, 10), 2: (3, None, None)}
    base = {0: 0, 1: 5, 2: 10}
    n_steps = 14

    def active_at(step):
        out = []
        for rid, (j, l, rj) in sorted(plan.items()):
            on = step >= j and (l is None or step < l or
                                (rj is not None and step >= rj))
            if on:
                out.append(rid)
        return out

    def run_legacy():
        pool = _mk_pool(cfg, nb)
        hist = {r: [11 + r] for r in plan}
        ctx = {r: 0 for r in plan}
        for step in range(n_steps):
            rids = active_at(step)
            np_pages = max(ctx[r] // BS + 1 for r in rids)
            B = len(plan)
            bt = np.full((B, np_pages), nb - 1, np.int32)
            cl = np.zeros((B,), np.int32)
            tk = np.zeros((B,), np.int32)
            for i, r in enumerate(rids):
                ids = blocks_of(base[r], ctx[r])
                bt[i, :len(ids)] = ids
                cl[i] = ctx[r]
                tk[i] = hist[r][-1]
            nxt, _, pool = paged_decode_step(
                params, pool, jnp.asarray(bt), jnp.asarray(cl),
                jnp.asarray(tk), cfg=cfg)
            nxt = np.asarray(nxt)
            for i, r in enumerate(rids):
                hist[r].append(int(nxt[i]))
                ctx[r] += 1
        return hist

    def run_runner():
        pool = _mk_pool(cfg, nb)
        runner = DecodeRunner({"cfg": cfg, "params": params},
                              block_size=BS, trash_block=nb - 1)
        hist = {r: [11 + r] for r in plan}
        ctx = {r: 0 for r in plan}
        for step in range(n_steps):
            rids = active_at(step)
            views = [DecodeRequestView(r, blocks_of(base[r], ctx[r]),
                                       hist[r]) for r in rids]
            pool = runner.decode(views, pool)
            for r in rids:
                ctx[r] += 1
        runner.flush()
        return hist

    legacy, ours = run_legacy(), run_runner()
    for r in plan:
        assert ours[r] == legacy[r], f"rid {r} tokens diverged"


def test_swap_round_trip_bit_exact_and_kernel_parity(model):
    """Donation safety: after a swap-out/swap-in round trip the pool is
    bit-identical, and the multi-page-tile kernel still matches the
    pure-jnp reference on the round-tripped pool."""
    cfg, params = model
    spec = PoolSpec(n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.resolved_head_dim, block_size=BS,
                    num_gpu_blocks=10, num_cpu_blocks=10)
    pools = PagedPools(spec)
    key = jax.random.PRNGKey(5)
    pools.gpu = jax.random.normal(key, pools.gpu.shape).astype(jnp.bfloat16)
    snap = np.asarray(pools.gpu, np.float32)

    used = [1, 3, 4, 6]
    pools.copy_out(used, [0, 1, 2, 3])
    pools.gpu = jnp.zeros_like(pools.gpu)
    pools.copy_in([0, 1, 2, 3], used)
    got = np.asarray(pools.gpu, np.float32)
    np.testing.assert_array_equal(got[:, :, used], snap[:, :, used])

    # kernel vs reference on the round-tripped pool's last layer, 2-page
    # tiles, ragged last tile
    layer = cfg.n_layers - 1
    kp, vp = pools.gpu[layer, 0], pools.gpu[layer, 1]
    q = jax.random.normal(key, (2, cfg.n_heads, cfg.resolved_head_dim),
                          jnp.bfloat16)
    bt = jnp.asarray([[1, 3, 4], [6, 4, 1]], jnp.int32)
    ctx = jnp.asarray([3 * BS, 2 * BS - 1], jnp.int32)
    scale = cfg.resolved_head_dim ** -0.5
    out = paged_attention(q, pools.gpu, layer, bt, ctx, scale,
                          pages_per_tile=2)
    ref = paged_attention_ref(q, jnp.stack([kp, vp]), bt, ctx, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_decode_after_swap_round_trip_matches_no_swap(model):
    """Pool donation + the swap channel: swapping a request's KV out and
    back mid-generation must not change any subsequent token."""
    cfg, params = model
    spec = PoolSpec(n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.resolved_head_dim, block_size=BS,
                    num_gpu_blocks=12, num_cpu_blocks=12)
    n_steps, swap_at = 10, 5

    def run(with_swap):
        pools = PagedPools(spec)
        runner = DecodeRunner({"cfg": cfg, "params": params},
                              block_size=BS, trash_block=11)
        hist = [3]
        for ctx in range(n_steps):
            if with_swap and ctx == swap_at:
                runner.flush()
                used = list(range(ctx // BS + 1))
                pools.copy_out(used, used)
                pools.gpu = jnp.zeros_like(pools.gpu)
                pools.copy_in(used, used)
            blocks = list(range(ctx // BS + 1))
            pools.gpu = runner.decode(
                [DecodeRequestView(0, blocks, hist)], pools.gpu)
        runner.flush()
        return hist

    assert run(with_swap=True) == run(with_swap=False)


def test_turn_boundary_context_jump_same_bucket(model):
    """A request whose context jumps OUTSIDE the decode loop (turn-end →
    sleep → re-admission prefill extends the history) while its rid never
    leaves the decode batch must be re-registered: the new page count
    stays inside the old bucket, so no rebuild masks a stale device
    ctx/token (regression: review finding on _update_rows)."""
    cfg, params = model
    nb = 8
    key = jax.random.PRNGKey(1)

    def prefill_write(pool, hist):
        # engine-style re-prefill: KV for all but the last history token
        from repro.models.paged import prefill_kv
        _, k, v = prefill_kv(params, jnp.asarray([hist[:-1]], jnp.int32),
                             cfg=cfg)
        k, v = np.asarray(k), np.asarray(v)
        T = k.shape[1]
        for t0 in range(0, T, BS):
            t1 = min(t0 + BS, T)
            blk = t0 // BS
            pool = pool.at[:, 0, blk, :t1 - t0].set(
                jnp.asarray(k[:, t0:t1], jnp.bfloat16))
            pool = pool.at[:, 1, blk, :t1 - t0].set(
                jnp.asarray(v[:, t0:t1], jnp.bfloat16))
        return pool

    turn2_prompt = [101, 202]
    n1, n2 = 10, 4            # turn 1 reaches pages 3 (bucket 4); turn 2
                              # starts at pages 4 — same bucket, no rebuild

    def run_legacy():
        pool = _mk_pool(cfg, nb)
        hist = [5]
        ctx = 0
        for _ in range(n1):
            bt = jnp.asarray([list(range(ctx // BS + 1))], jnp.int32)
            nxt, _, pool = paged_decode_step(
                params, pool, bt, jnp.asarray([ctx], jnp.int32),
                jnp.asarray([hist[-1]], jnp.int32), cfg=cfg)
            hist.append(int(nxt[0]))
            ctx += 1
        hist.extend(turn2_prompt)
        pool = prefill_write(pool, hist)
        ctx = len(hist) - 1
        for _ in range(n2):
            bt = jnp.asarray([list(range(ctx // BS + 1))], jnp.int32)
            nxt, _, pool = paged_decode_step(
                params, pool, bt, jnp.asarray([ctx], jnp.int32),
                jnp.asarray([hist[-1]], jnp.int32), cfg=cfg)
            hist.append(int(nxt[0]))
            ctx += 1
        return hist

    def run_runner():
        pool = _mk_pool(cfg, nb)
        runner = DecodeRunner({"cfg": cfg, "params": params},
                              block_size=BS, trash_block=nb - 1)
        hist = [5]
        ctx = 0
        for _ in range(n1):
            pool = runner.decode(
                [DecodeRequestView(0, list(range(ctx // BS + 1)), hist)],
                pool)
            ctx += 1
        runner.flush()            # engine flushes before reading history
        hist.extend(turn2_prompt)
        pool = prefill_write(pool, hist)
        ctx = len(hist) - 1
        for _ in range(n2):
            pool = runner.decode(
                [DecodeRequestView(0, list(range(ctx // BS + 1)), hist)],
                pool)
            ctx += 1
        runner.flush()
        assert runner.stats.rebuilds == 3      # buckets 1, 2, 4 — no 4th
        return hist

    assert run_runner() == run_legacy()


def test_runner_prefill_matches_host_write_path(model):
    """Runner-managed prefill insertion (jitted bucketed scatter +
    direct row registration) vs the legacy host path
    (``PagedPools.write_tokens``-style per-block writes + exact-shape
    decode): bit-identical token streams across a prefill, a decode
    stretch, a turn-boundary re-prefill and another decode stretch."""
    cfg, params = model
    nb = 16
    prompt = [int(x) for x in
              np.random.RandomState(3).randint(1, cfg.vocab_size, 9)]
    turn2 = [101, 202, 303]
    n1, n2 = 6, 4

    def legacy():
        from repro.models.paged import prefill_kv
        pool = _mk_pool(cfg, nb)
        hist = list(prompt)

        def host_prefill(pool, toks):
            logits, k, v = prefill_kv(params,
                                      jnp.asarray([toks], jnp.int32), cfg=cfg)
            k, v = np.asarray(k), np.asarray(v)
            for t0 in range(0, k.shape[1], BS):
                t1 = min(t0 + BS, k.shape[1])
                blk = t0 // BS
                pool = pool.at[:, 0, blk, :t1 - t0].set(
                    jnp.asarray(k[:, t0:t1], jnp.bfloat16))
                pool = pool.at[:, 1, blk, :t1 - t0].set(
                    jnp.asarray(v[:, t0:t1], jnp.bfloat16))
            return pool, logits

        def decode(pool, hist, steps):
            for _ in range(steps):
                ctx = len(hist) - 1
                bt = jnp.asarray([list(range(ctx // BS + 1))], jnp.int32)
                nxt, _, pool = paged_decode_step(
                    params, pool, bt, jnp.asarray([ctx], jnp.int32),
                    jnp.asarray([hist[-1]], jnp.int32), cfg=cfg)
                hist.append(int(nxt[0]))
            return pool

        pool, logits = host_prefill(pool, hist)
        hist.append(int(np.argmax(np.asarray(logits))))
        pool = decode(pool, hist, n1)
        hist.extend(turn2)
        pool, logits = host_prefill(pool, hist)
        hist.append(int(np.argmax(np.asarray(logits))))
        decode(pool, hist, n2)
        return hist

    def runner_path():
        from repro.kernels.ops import insert_prefill_cache_size
        pool = _mk_pool(cfg, nb)
        runner = DecodeRunner({"cfg": cfg, "params": params},
                              block_size=BS, trash_block=nb - 1)
        c0 = insert_prefill_cache_size()
        hist = list(prompt)

        def blocks(ctx):
            return list(range(ctx // BS + 1))

        pool = runner.prefill(
            DecodeRequestView(0, blocks(len(hist) - 1), hist), pool,
            emit_first=True)
        for _ in range(n1):
            ctx = len(hist) - 1       # flush() inside decode keeps this
            pool = runner.decode(     # current: single-request lockstep
                [DecodeRequestView(0, blocks(ctx), hist)], pool)
            runner.flush()
        hist.extend(turn2)
        pool = runner.prefill(
            DecodeRequestView(0, blocks(len(hist) - 1), hist), pool,
            emit_first=True)
        for _ in range(n2):
            ctx = len(hist) - 1
            pool = runner.decode(
                [DecodeRequestView(0, blocks(ctx), hist)], pool)
            runner.flush()
        assert runner.stats.prefills == 2
        # shape-bucketed insert: one compiled variant per pow2 page bucket
        assert insert_prefill_cache_size() - c0 <= \
            math.ceil(math.log2(nb)) + 1
        return hist

    assert runner_path() == legacy()


def test_first_token_draw_builds_no_program_after_the_first(model):
    """The prefill's first-token draw is one compiled program: once it
    is built, later draws of other tokens trace, lower and build
    nothing (called eagerly, the sampler's ``lax.cond`` is lowered again
    on every draw)."""
    from repro.core import tracing
    cfg, params = model
    nb = 16
    pool = _mk_pool(cfg, nb)
    runner = DecodeRunner({"cfg": cfg, "params": params},
                          block_size=BS, trash_block=nb - 1)
    tr = tracing.tracer()
    built, firsts = [], []
    for rid in range(3):
        hist = [int(x) for x in np.random.RandomState(10 + rid).randint(
            1, cfg.vocab_size, 9)]
        view = DecodeRequestView(rid, [3 * rid, 3 * rid + 1, 3 * rid + 2],
                                 hist)
        total = runner.prefill_begin(view, emit_first=True)
        staged = runner.prefill_chunk_compute(rid, total)
        runner.prefill_emit_first(rid)
        draw = [s for s in tr.spans()
                if s.name == "prefill.first_token" and s.rid == rid][-1]
        built.append(sorted(s.name for s in tr.spans()
                            if s.parent == draw.id))
        firsts.append(hist[-1])
        pool = runner.prefill_insert(view, pool, staged)
        assert len(hist) == 10
    assert built[1:] == [[], []]
    assert len(set(firsts)) == 3          # three different tokens drawn


def test_flush_is_idempotent_and_deferred(model):
    cfg, params = model
    pool = _mk_pool(cfg, 4)
    runner = DecodeRunner({"cfg": cfg, "params": params},
                          block_size=BS, trash_block=3)
    hist = [9]
    pool = runner.decode([DecodeRequestView(0, [0], hist)], pool)
    assert len(hist) == 1          # token still on device
    runner.flush()
    assert len(hist) == 2          # materialized exactly once
    runner.flush()
    assert len(hist) == 2
    assert runner.stats.host_syncs == 1


def test_row_writes_build_nothing_after_the_tables_and_land_exactly(model):
    """Every count of changed rows is written by a program built with the
    tables (one per pow2 count), so row writes after ``_rebuild`` trace,
    lower and build nothing; each write lands on its rows alone, a
    block-table-only row keeping its counters."""
    from repro.core import tracing
    cfg, params = model
    runner = DecodeRunner({"cfg": cfg, "params": params},
                          block_size=BS, trash_block=15)
    runner._rebuild([], 4, 8)
    runner._row_key(0)                  # the key fold's program
    tr = tracing.tracer()
    seen = {s.id for s in tr.spans()}
    want_bt = np.full((8, 4), 15, np.int32)
    want_ctx = np.zeros((8,), np.int32)
    want_tok = np.zeros((8,), np.int32)
    want_keys = np.zeros((8, 2), np.uint32)
    for n in range(1, 9):
        rows = list(range(8 - n, 8))
        pending = {}
        for j, r in enumerate(rows):
            ids = tuple(range(j % 4 + 1))
            want_bt[r] = 15
            want_bt[r, :len(ids)] = ids
            if j % 2:                   # block table only
                pending[r] = (ids, None, None, None, None)
            else:
                pending[r] = (ids, 10 * n + j, 100 + r,
                              np.asarray([n, r], np.uint32),
                              np.asarray([0.0, 0.0, 1.0], np.float32))
                want_ctx[r], want_tok[r] = 10 * n + j, 100 + r
                want_keys[r] = n, r
        runner._scatter_rows(pending)
        np.testing.assert_array_equal(np.asarray(runner._bt), want_bt)
        np.testing.assert_array_equal(np.asarray(runner._ctx), want_ctx)
        np.testing.assert_array_equal(np.asarray(runner._tok), want_tok)
        np.testing.assert_array_equal(np.asarray(runner._keys), want_keys)
    built = [s.name for s in tr.spans() if s.id not in seen
             and s.name.split(":")[0] in ("trace", "lower", "build")]
    assert built == []
