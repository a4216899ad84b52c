"""Device-resident decode runner — the real-mode token hot path.

The engine's original decode loop paid three per-token costs that dwarf
the swap overheads FastSwitch optimizes: (1) ``paged_decode_step``
recompiled whenever the longest running request crossed a page boundary
(the block-table width was the exact max page count), (2) the whole KV
pool was copied every step because the jitted step returned it without
buffer donation, and (3) every iteration rebuilt block tables in Python,
re-uploaded them, and blocked on a device->host sync to pull each next
token out with ``int(nxt[i])``.

The DecodeRunner keeps the entire per-step decode state ON DEVICE and
fixed-shape (DESIGN.md §3):

  * **Shape bucketing** — the block-table width (pages) and the batch
    dimension are rounded up to powers of two with high-water retention,
    so a context that grows across P page boundaries triggers
    O(log2(P)) compilations instead of O(P).
  * **Persistent block tables** — a (B_bucket, pages_bucket) int32 array
    lives on device; each step only the rows whose block lists changed
    since the last step are scattered in (typically one row per bs
    tokens per request).  Context lengths and last-token ids advance on
    device inside the jitted step (``active`` mask), so steady state
    uploads nothing at all.
  * **Pool donation** — ``paged_decode_step_device`` donates pool,
    context and token arrays; the per-layer KV write is in-place.
  * **Compute-dtype weights** — the step reads a view of the params
    with the layer matrices converted to the compute dtype once, at the
    runner's build (``models.paged.decode_weights``), so no step re-casts
    a weight table; prefill reads the params as given.
  * **Deferred host sync** — the next-token array is NOT pulled to the
    host at dispatch.  It is materialized lazily (``flush``) at the
    start of the NEXT decode — after the engine's control plane for that
    iteration has already run — so scheduling overlaps the in-flight
    device step.  Anyone reading ``token_history`` must flush first.

The runner is also the single owner of the rest of the real-mode token
pipeline (DESIGN.md §3.5/§3.6):

  * **Runner-managed prefill insertion** — ``prefill()`` computes KV for
    a (re-)admitted request and scatters it into the donated pool
    through the block table with a jitted, shape-bucketed insert
    (``kernels.ops.insert_prefill``), then registers the row directly in
    the persistent device tables; the engine no longer round-trips
    prefill KV through the host (``PagedPools.write_tokens``).
  * **Chunked prefill state machine** (DESIGN.md §5) — ``prefill_begin /
    prefill_chunk_compute / prefill_chunk_insert / prefill_finish /
    prefill_abort``: long prompts are processed as pow2-bucketed,
    position-masked chunks (``kernels.ops.prefill_chunk``) whose KV is
    carried chunk to chunk on device and inserted block-aligned into the
    pool, so the engine can interleave decode iterations between chunks
    and prompt-length variety compiles O(log^2) prefill variants instead
    of one per length.  The whole-prompt ``prefill()`` path is the same
    machinery run as a single chunk — one bit-exact forward for both.
  * **Device-side sampling** — temperature/top-k/top-p sampling is fused
    into the decode step with a per-row on-device array of base PRNG
    keys; the step folds the position in, so the random stream is a pure
    function of (seed, rid, position).  The parameters ride a PER-ROW
    traced (B, 3) array maintained with the row state, so every request
    carries its own ``SamplingParams`` while greedy (temperature 0,
    bit-exact argmax) and sampled rows share one compiled variant per
    bucket and the deferred sync stays one token array per step.
  * **Mesh sharding** (DESIGN.md §9) — with a ``mesh`` the decode step,
    chunked prefill and fused sampler run tensor-parallel under
    ``shard_map``: q/k/v projections and the KV pool are head-sharded
    over ``model``, head outputs are all-gathered (a pure concat) ahead
    of the replicated output projection, and no float reduction ever
    crosses shards — token streams are bit-identical to single-device.

Row-occupancy invariant: a row is either *registered* (owned by a live
request, block table = its pages) or *freed* (block table = trash page,
context 0) — freed rows still execute the step, but their masked output
is discarded and their KV write lands in the reserved trash block.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing
from repro.kernels import ops
from repro.models.paged import (decode_weights, paged_decode_step_device,
                                paged_decode_step_device_sharded,
                                sample_tokens)


def next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


# the per-row PRNG key fold as one program, not a chain of eager ops
_fold_in = jax.jit(jax.random.fold_in)


@jax.jit
def _write_rows(bt, ctx, tok, keys, smp, rows, bt_rows, full, ctx_v, tok_v,
                key_v, smp_v):
    """The decode tables with ``rows`` rewritten in one program: each
    row's block table, and the context, token, key and sampling row of
    the rows ``full`` marks (the others keep theirs).  A row may repeat
    with the same values (the pow2 padding), so the write order is
    moot."""
    def put(a, v):
        m = full.reshape((-1,) + (1,) * (v.ndim - 1))
        return a.at[rows].set(jnp.where(m, v, a[rows]))
    return (bt.at[rows].set(bt_rows), put(ctx, ctx_v), put(tok, tok_v),
            put(keys, key_v), put(smp, smp_v))


@jax.jit
def _draw_first(logits, key, pos, sampling):
    """A prefill's first-token draw as one compiled program: called
    eagerly, ``sample_tokens`` traces and lowers its ``lax.cond`` again
    on every call.  logits (V,); key (2,) u32; pos () i32; sampling (3,)
    f32 -> () i32."""
    return sample_tokens(logits[None, :], key[None, :], pos[None],
                         sampling[None, :])[0]


@dataclass
class DecodeRequestView:
    """What the runner needs to know about one decoding request."""
    rid: int
    block_ids: Sequence[int]       # GPU pages covering context+1 tokens
    token_history: List[int]       # shared list; flush() appends to it
    # per-request (temperature, top_k, top_p); None = runner default.
    # Rides the row state as one (3,) f32 slot of the (B, 3) sampling
    # array the fused sampler traces — any mix of per-request configs
    # shares ONE compiled variant per bucket.
    sampling: Optional[Tuple[float, float, float]] = None


@dataclass
class RunnerStats:
    steps: int = 0
    rebuilds: int = 0              # bucket growth -> full state re-upload
    rows_updated: int = 0          # incremental row scatters
    host_syncs: int = 0            # deferred next-token materializations
    prefills: int = 0              # runner-managed prefill insertions
    prefill_chunks: int = 0        # chunked-prefill forward launches
    prefill_tokens: int = 0        # prompt tokens actually forwarded
    prefill_aborts: int = 0        # mid-prefill preemptions


@dataclass
class _PrefillState:
    """One in-flight (possibly chunked) prefill (DESIGN.md §5).

    ``k_carry``/``v_carry`` hold the per-layer K/V computed so far —
    pow2-bucketed device buffers the chunk forward appends to and
    attends against; ``pos`` counts real tokens processed.  The state
    lives across engine iterations while decode steps interleave with
    the remaining chunks, and is dropped whole on a mid-prefill
    preemption (``prefill_abort``)."""
    view: DecodeRequestView
    toks: List[int]                # tokens to process (hist, or hist[:-1])
    emit_first: bool
    pos: int = 0                   # real tokens already processed
    k_carry: Optional[jnp.ndarray] = None     # (L, S_pad, Hkv, D)
    v_carry: Optional[jnp.ndarray] = None
    last_logits: Optional[jnp.ndarray] = None # (V,) at the last real pos
    emitted: bool = False          # first token already appended to hist


class DecodeRunner:
    def __init__(self, model_bundle: dict, *, block_size: int,
                 trash_block: int, min_pages_bucket: int = 1,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 1.0, seed: int = 0, mesh=None,
                 device=None):
        self.mb = model_bundle
        self.bs = block_size
        self.trash = trash_block
        self._min_pages = max(1, min_pages_bucket)
        # ``mesh``: a ("data", "model") jax mesh — the decode / prefill
        # steps then run tensor-parallel under ``shard_map`` with the
        # q/k/v projections and the KV pool head-sharded (DESIGN.md §9).
        # A 1-device mesh is normalized to None: the single-device step
        # is byte-identical to the pre-mesh code and the sharded path
        # degrades to it bit-exactly.
        if mesh is not None and mesh.size == 1:
            mesh = None
        self._mesh = mesh
        # ``device``: without a mesh, the params and every persistent
        # decode array live here (a no-op move when the bundle already
        # does); a replica on another chip gets its own params copy
        self._device = device
        self._params = model_bundle["params"]
        if device is not None and mesh is None:
            self._params = jax.device_put(self._params, device)
        if mesh is not None:
            from repro.models.paged import shardable_heads
            from repro.models.sharding import serving_param_pspecs
            cfg = model_bundle["cfg"]
            assert shardable_heads(cfg, mesh.shape["model"]), (
                cfg.name, dict(mesh.shape))
            self._params = jax.tree.map(
                lambda x, s: jax.device_put(
                    x, jax.sharding.NamedSharding(mesh, s)),
                self._params, serving_param_pspecs(self._params))
        # the decode step reads its weights in the dtype it computes in,
        # converted once here (``decode_weights``); prefill keeps
        # ``self._params``, the tree its callers compile prefill with
        self._decode_params, weights = decode_weights(
            self._params, model_bundle["cfg"])
        self._trace = tracing.tracer()
        self._trace.mark("decode.weights", **weights)
        # sampling config: the runner-wide default row of the per-row
        # (B, 3) [temperature, top_k, top_p] array the fused sampler
        # traces (values never force a compile — the shape follows the
        # bucket), + the base PRNG key the per-row keys fold from
        self._default_sampling = np.asarray(
            [temperature, float(top_k), top_p], np.float32)
        self._base_key = jax.random.PRNGKey(seed)
        # bucket high-water marks (never shrink: shrinking would thrash
        # the jit cache for no memory win at these sizes)
        self._pages_bucket = 0
        self._batch_bucket = 0
        # host mirrors of device state
        self._rows: Dict[int, int] = {}               # rid -> row
        self._row_blocks: List[Tuple[int, ...]] = []  # what device bt holds
        self._row_ctx: List[int] = []
        self._free: List[int] = []
        # device state
        self._bt = None                               # (B, P) int32
        self._ctx = None                              # (B,) int32
        self._tok = None                              # (B,) int32
        self._keys = None                             # (B, 2) uint32
        self._active = None                           # (B,) bool
        self._sampling = None                         # (B, 3) f32
        self._active_rows: frozenset = frozenset()
        # deferred next-token sync: ([(row, token_history)], device array)
        self._pending: Optional[Tuple[list, jnp.ndarray]] = None
        # in-flight chunked prefills, keyed by rid (DESIGN.md §5)
        self._prefills: Dict[int, _PrefillState] = {}
        self.stats = RunnerStats()

    @property
    def batch_bucket(self) -> int:
        """Compiled decode-batch bucket (0 before the first step).  The
        step always executes this many padded rows, so admitting requests
        up to the bucket adds NO compile and NO step cost — the engine's
        batch-bucket-aware admission targets exactly this size."""
        return self._batch_bucket

    def _row_key(self, rid: int, salt: int = 0):
        """Position-independent per-row base PRNG key, folded from
        (seed, rid).  The decode step folds the position in on device
        (``sample_tokens``), so the sampled stream is a pure function of
        (seed, rid, position) — reproducible under any preemption order,
        row re-registration or bucket rebuild.  ``salt`` separates the
        prefill first-token draw from the row's decode stream."""
        k = _fold_in(self._base_key, rid)
        return _fold_in(k, salt) if salt else k

    def _row_sampling(self, view: DecodeRequestView) -> np.ndarray:
        """The (3,) f32 [temperature, top_k, top_p] row for ``view`` —
        its per-request override, or the runner default."""
        if view.sampling is None:
            return self._default_sampling
        t, k, p = view.sampling
        return np.asarray([t, float(k), p], np.float32)

    # ------------------------------------------------------------------
    # deferred host sync
    # ------------------------------------------------------------------

    def flush(self) -> None:
        """Materialize the previous step's next tokens into the request
        histories.  One device sync for the whole batch; by the time the
        engine calls this (start of the next decode, or before reading a
        history) the device step has usually already finished."""
        if self._pending is None:
            return
        with self._trace.span("decode.flush"):
            rows_hist, nxt = self._pending
            self._pending = None
            # fslint: disable=FS003(the deferred-sync point: ONE batched d2h pull per step, by design)
            vals = np.asarray(nxt)
            self.stats.host_syncs += 1
            for row, hist in rows_hist:
                hist.append(int(vals[row]))

    # ------------------------------------------------------------------
    # device-state maintenance
    # ------------------------------------------------------------------

    def _rebuild(self, views: List[DecodeRequestView],
                 pages_bucket: int, batch_bucket: int) -> None:
        """Bucket grew: re-upload the whole (small) control state."""
        self._pages_bucket, self._batch_bucket = pages_bucket, batch_bucket
        self.stats.rebuilds += 1
        self._rows = {}
        self._row_blocks = [()] * batch_bucket
        self._row_ctx = [0] * batch_bucket
        bt = np.full((batch_bucket, pages_bucket), self.trash, np.int32)
        ctx = np.zeros((batch_bucket,), np.int32)
        tok = np.zeros((batch_bucket,), np.int32)
        keys = np.zeros((batch_bucket, 2), np.uint32)
        act = np.zeros((batch_bucket,), bool)
        smp = np.zeros((batch_bucket, 3), np.float32)
        for i, v in enumerate(views):
            ids = tuple(v.block_ids)
            self._rows[v.rid] = i
            self._row_blocks[i] = ids
            self._row_ctx[i] = len(v.token_history) - 1
            bt[i, :len(ids)] = ids
            ctx[i] = self._row_ctx[i]
            tok[i] = v.token_history[-1]
            # fslint: disable=FS003(rebuild-time row-key pull, a few bytes outside the steady-state step)
            keys[i] = np.asarray(self._row_key(v.rid))
            act[i] = True
            smp[i] = self._row_sampling(v)
        self._free = list(range(len(views), batch_bucket))
        self._bt, self._ctx, self._tok, self._keys, self._active, \
            self._sampling = jax.device_put((bt, ctx, tok, keys, act, smp),
                                            self._device)
        self._active_rows = frozenset(range(len(views)))
        # build the row write of every pow2 row count for the new tables
        # now, not inside a serving step: each writes rows [0, n) with
        # the values they hold
        n = 1
        while n <= batch_bucket:
            self._bt, self._ctx, self._tok, self._keys, self._sampling = \
                _write_rows(self._bt, self._ctx, self._tok, self._keys,
                            self._sampling, np.arange(n, dtype=np.int32),
                            bt[:n], np.ones((n,), bool), ctx[:n], tok[:n],
                            keys[:n], smp[:n])
            n *= 2

    def _scatter_rows(self, pending: Dict[int, Tuple[Tuple[int, ...],
                                                     Optional[int],
                                                     Optional[int],
                                                     Optional[np.ndarray],
                                                     Optional[np.ndarray]]]
                      ) -> None:
        """One batched device scatter for the changed rows.  Entry value
        is (block_ids, ctx, tok, key_data, sampling_row); the trailing
        four are None for rows whose device counters are already right
        (block-table-only write)."""
        if not pending:
            return
        entries = sorted(pending.items())
        # pad to a pow2 count by repeating the first entry: one program
        # per pow2, all built with the tables (``_rebuild``)
        n = next_pow2(len(entries))
        entries += [entries[0]] * (n - len(entries))
        rows = np.asarray([r for r, _ in entries], np.int32)
        btrows = np.full((n, self._pages_bucket), self.trash, np.int32)
        full = np.zeros((n,), bool)
        ctx = np.zeros((n,), np.int32)
        tok = np.zeros((n,), np.int32)
        keys = np.zeros((n, 2), np.uint32)
        smp = np.zeros((n, 3), np.float32)
        for j, (_, (ids, c, t, kd, sr)) in enumerate(entries):
            btrows[j, :len(ids)] = ids
            if c is not None:
                full[j], ctx[j], tok[j] = True, c, t
                keys[j], smp[j] = np.asarray(kd, np.uint32), sr
        self._bt, self._ctx, self._tok, self._keys, self._sampling = \
            _write_rows(self._bt, self._ctx, self._tok, self._keys,
                        self._sampling, rows, btrows, full, ctx, tok, keys,
                        smp)
        self.stats.rows_updated += len(pending)

    def _update_rows(self, views: List[DecodeRequestView]) -> None:
        """Incremental path: scatter in only the rows that changed."""
        current = {v.rid for v in views}
        # per-row pending write, keyed by row so a free + immediate
        # re-register of the same row collapses to one write (duplicate
        # scatter indices have undefined order)
        pending: Dict[int, Tuple[Tuple[int, ...], Optional[int],
                                 Optional[int], Optional[np.ndarray],
                                 Optional[np.ndarray]]] = {}
        zero_key = np.zeros((2,), np.uint32)
        zero_smp = np.zeros((3,), np.float32)
        for rid in [r for r in self._rows if r not in current]:
            row = self._rows.pop(rid)
            self._row_blocks[row] = ()
            self._row_ctx[row] = 0
            self._free.append(row)
            # point at trash, mask off
            pending[row] = ((), 0, 0, zero_key, zero_smp)
        for v in views:
            ids = tuple(v.block_ids)
            row = self._rows.get(v.rid)
            hist_ctx = len(v.token_history) - 1
            if row is None:
                row = self._free.pop()
                self._rows[v.rid] = row
                self._row_blocks[row] = ids
                self._row_ctx[row] = hist_ctx
                pending[row] = (ids, hist_ctx, v.token_history[-1],
                                self._row_key(v.rid),
                                self._row_sampling(v))
            elif self._row_ctx[row] != hist_ctx:
                # context jumped outside the decode loop: a turn-boundary
                # re-admission extends the history and rewrites prefill KV
                # without the rid ever leaving the batch (no decode ran
                # while it slept, so the row was never freed) — the device
                # ctx/token are stale; full re-register
                self._row_blocks[row] = ids
                self._row_ctx[row] = hist_ctx
                pending[row] = (ids, hist_ctx, v.token_history[-1],
                                self._row_key(v.rid),
                                self._row_sampling(v))
            elif ids != self._row_blocks[row]:
                self._row_blocks[row] = ids       # page-boundary growth or
                pending[row] = (ids, None, None, None, None)  # swap-in move
        self._scatter_rows(pending)
        active = frozenset(self._rows[v.rid] for v in views)
        if active != self._active_rows:
            self._active_rows = active
            act = np.zeros((self._batch_bucket,), bool)
            act[list(active)] = True
            self._active = jax.device_put(act, self._device)

    # ------------------------------------------------------------------
    # the step
    # ------------------------------------------------------------------

    def decode(self, views: List[DecodeRequestView], pool):
        """Launch one decode step for ``views`` against ``pool``.

        Returns the new pool (the passed-in pool is DONATED — the caller
        must rebind its reference).  Next tokens stay on device until
        ``flush()``."""
        assert views, "decode() needs at least one request"
        self.flush()
        need_pages = max(len(v.block_ids) for v in views)
        pages_bucket = max(self._pages_bucket,
                           next_pow2(max(need_pages, self._min_pages)))
        batch_bucket = max(self._batch_bucket, next_pow2(len(views)))
        with self._trace.span("decode.rows"):
            if (pages_bucket != self._pages_bucket
                    or batch_bucket != self._batch_bucket):
                self._rebuild(views, pages_bucket, batch_bucket)
            else:
                self._update_rows(views)

        with self._trace.span("decode.launch"):
            # the attention kernel reads each row's live pages (its block
            # count, or one fewer where its next page is allocated early)
            # and a padding row's one trash page, of batch x pages
            self._trace.mark(
                "paged_attn.pages",
                live=(sum(len(v.block_ids) for v in views)
                      + batch_bucket - len(views)),
                bucket=batch_bucket * pages_bucket)
            if self._mesh is None:
                nxt, pool, self._ctx, self._tok = \
                    paged_decode_step_device(
                        self._decode_params, pool, self._bt, self._ctx,
                        self._tok, self._active, self._keys, self._sampling,
                        cfg=self.mb["cfg"])
            else:
                nxt, pool, self._ctx, self._tok = \
                    paged_decode_step_device_sharded(
                        self._decode_params, pool, self._bt, self._ctx,
                        self._tok, self._active, self._keys, self._sampling,
                        cfg=self.mb["cfg"], mesh=self._mesh)
        self._pending = ([(self._rows[v.rid], v.token_history)
                          for v in views], nxt)
        for v in views:
            self._row_ctx[self._rows[v.rid]] += 1
        self.stats.steps += 1
        return pool

    # ------------------------------------------------------------------
    # runner-managed prefill insertion
    # ------------------------------------------------------------------

    def _register(self, view: DecodeRequestView) -> bool:
        """Write a (re-)admitted request's row state straight through the
        persistent device tables so the next decode uploads nothing.
        Returns False when the current buckets can't hold the row — the
        next decode()'s rebuild picks it up from the views instead."""
        if self._bt is None:
            return False
        ids = tuple(view.block_ids)
        hist_ctx = len(view.token_history) - 1
        if len(ids) > self._pages_bucket:
            return False
        row = self._rows.get(view.rid)
        if row is None:
            if not self._free:
                return False
            row = self._free.pop()
            self._rows[view.rid] = row
        self._row_blocks[row] = ids
        self._row_ctx[row] = hist_ctx
        self._scatter_rows({row: (ids, hist_ctx, view.token_history[-1],
                                  self._row_key(view.rid),
                                  self._row_sampling(view))})
        return True

    def release(self, rid: int) -> None:
        """Free an aborted request's row immediately: block table back to
        the trash sentinel, context zeroed, row masked off and returned
        to the free list.  Any open chunked-prefill state is dropped too.
        (The lazy path — ``_update_rows`` at the next decode — only frees
        rows for rids absent from the views; an abort must not wait for a
        decode that may never come.)"""
        self._prefills.pop(rid, None)
        row = self._rows.pop(rid, None)
        if row is None:
            return
        self._row_blocks[row] = ()
        self._row_ctx[row] = 0
        self._free.append(row)
        with self._trace.span("decode.rows", rid):
            self._scatter_rows({row: ((), 0, 0, np.zeros((2,), np.uint32),
                                      np.zeros((3,), np.float32))})
            if row in self._active_rows:
                self._active_rows = self._active_rows - {row}
                act = np.zeros((self._batch_bucket,), bool)
                act[list(self._active_rows)] = True
                self._active = jax.device_put(act, self._device)

    def invariant_report(self, live_rids) -> List[str]:
        """Row-map validation for the engine sanitizer (DESIGN.md §7).
        Returns violation strings (empty = clean): registered rows and
        free rows must partition the batch bucket exactly; registered
        rows belong to live rids; freed rows carry the trash sentinel's
        empty host mirror; active rows are registered."""
        v: List[str] = []
        if self._batch_bucket == 0:
            if self._rows or self._free:
                v.append("D1: runner rows exist before first bucket build")
            return v
        reg = set(self._rows.values())
        free = set(self._free)
        if len(self._free) != len(free):
            v.append(f"D1: duplicate rows in free list {self._free}")
        if len(reg) != len(self._rows):
            v.append(f"D1: two rids share a runner row {self._rows}")
        if reg & free:
            v.append(f"D1: rows both registered and free: {reg & free}")
        if reg | free != set(range(self._batch_bucket)):
            v.append(f"D1: rows {reg | free} do not partition bucket "
                     f"{self._batch_bucket}")
        live = set(live_rids)
        for rid, row in self._rows.items():
            if rid not in live:
                v.append(f"D2: runner row {row} registered to dead rid "
                         f"{rid}")
        for row in free:
            if self._row_blocks[row] != () or self._row_ctx[row] != 0:
                v.append(f"D2: freed row {row} still carries blocks="
                         f"{self._row_blocks[row]} ctx={self._row_ctx[row]}")
        for row in self._active_rows:
            if row not in reg:
                v.append(f"D2: active row {row} not registered")
        return v

    # -- chunked prefill state machine (DESIGN.md §5) -------------------

    def prefill_begin(self, view: DecodeRequestView, *,
                      emit_first: bool, reused_tokens: int = 0,
                      pool=None) -> int:
        """Open a (possibly chunked) prefill for ``view``: the runner
        will compute KV for the view's history (all of it with
        ``emit_first`` — a fresh turn; all but the pending last token on
        a recompute re-prefill) chunk by chunk through the bucketed
        position-masked forward.

        ``reused_tokens`` > 0 with a ``pool``: the first
        ``reused_tokens`` positions' KV is already RESIDENT in the pool
        (the reuse mechanism's restored prefix) — the carry is seeded
        from it (``ops.seed_prefill_carry``, bit-identical to
        recomputing) and chunking starts at the block-aligned floor of
        ``reused_tokens``, so re-admissions neither recompute nor
        re-bill the prefix.  The caller must hold the pool lock (the
        seed gather reads the pool).

        Returns the token count left TO PROCESS
        (``prefill_chunk_compute`` consumes it)."""
        assert self.bs & (self.bs - 1) == 0, \
            f"chunked prefill needs a pow2 block size, got {self.bs}"
        self.flush()              # history must be current before reading
        hist = view.token_history
        toks = hist if emit_first else hist[:-1]
        start = 0
        k_c = v_c = None
        if reused_tokens > 0 and pool is not None:
            start = min(reused_tokens - reused_tokens % self.bs,
                        len(toks) - 1)      # always >= 1 token to process
            start = max(start - start % self.bs, 0)
            if start > 0:
                k_c, v_c = ops.seed_prefill_carry(
                    pool, view.block_ids, start, trash=self.trash)
        self._prefills[view.rid] = _PrefillState(
            view=view, toks=list(toks), emit_first=emit_first, pos=start,
            k_carry=k_c, v_carry=v_c)
        return len(toks) - start

    def prefill_pending(self, rid: int) -> int:
        """Tokens the open prefill for ``rid`` has left to process."""
        st = self._prefills[rid]
        return len(st.toks) - st.pos

    @tracing.traced("prefill.compute")
    def prefill_chunk_compute(self, rid: int, n_tokens: int) -> Optional[Tuple]:
        """Compute KV for the next ``n_tokens`` of the open prefill: one
        bucketed chunk forward attending the carry buffers (bit-exact
        with the monolithic path — see ``models.paged.prefill_kv_chunk``).
        Non-final chunks must be block-size multiples so every chunk's
        pool insert stays block-aligned.  Touches NO pool state, so the
        engine runs it OUTSIDE the pool lock.  Returns the staged
        (k, v, blocks) for ``prefill_chunk_insert``."""
        st = self._prefills[rid]
        if n_tokens <= 0:
            return None
        bs = self.bs
        assert st.pos % bs == 0, \
            f"chunk start {st.pos} not block-aligned (bs={bs})"
        assert st.pos + n_tokens <= len(st.toks), (st.pos, n_tokens)
        chunk = st.toks[st.pos:st.pos + n_tokens]
        st.last_logits, st.k_carry, st.v_carry, k_c, v_c = \
            ops.prefill_chunk(self._params, chunk, st.k_carry,
                              st.v_carry, st.pos, cfg=self.mb["cfg"],
                              block_size=bs, mesh=self._mesh)
        c_pad = k_c.shape[1]
        n_pages = -(-n_tokens // bs)
        blocks = np.full((c_pad // bs,), self.trash, np.int32)
        b0 = st.pos // bs
        blocks[:n_pages] = list(st.view.block_ids)[b0:b0 + n_pages]
        st.pos += n_tokens
        self.stats.prefill_chunks += 1
        self.stats.prefill_tokens += n_tokens
        return k_c, v_c, blocks

    def prefill_chunk_insert(self, rid: int, pool, staged):
        """Scatter one staged chunk into the DONATED pool through the
        block table (jitted, shape-bucketed — the existing staged insert
        path).  Run under the pool lock; the caller must rebind its pool
        reference to the returned array."""
        if staged is None:
            return pool
        with self._trace.span("prefill.insert", rid):
            k, v, blocks = staged
            return ops.insert_prefill(pool, k, v, blocks, self.bs)

    def _prefill_emit(self, st: _PrefillState) -> None:
        """Emit the response's first token from the final chunk's last
        real position (sampled on device per the runner's sampling
        config; bit-exact greedy argmax at temperature 0)."""
        if not st.emit_first or st.emitted:
            return
        with self._trace.span("prefill.first_token", st.view.rid):
            hist = st.view.token_history
            tok = _draw_first(st.last_logits,
                              self._row_key(st.view.rid, salt=1),
                              np.int32(len(hist)),
                              self._row_sampling(st.view))
            # fslint: disable=FS003(first-token emit must sync: the token gates scheduling and streaming)
            hist.append(int(tok))
        st.emitted = True

    def prefill_finish(self, rid: int) -> None:
        """Close a fully-processed prefill: emit the first token (fresh
        turns), register the row in the persistent device tables, and
        drop the carry buffers."""
        st = self._prefills.pop(rid)
        assert st.pos == len(st.toks), \
            f"prefill_finish with {len(st.toks) - st.pos} tokens pending"
        self._prefill_emit(st)
        self.stats.prefills += 1
        with self._trace.span("decode.rows", rid):
            self._register(st.view)

    def prefill_abort(self, rid: int) -> None:
        """Mid-prefill preemption: drop the carry buffers and the state.
        The processed prefix KV already sits in the pool (the engine
        swap-outs what it wants to keep); resumption re-opens a fresh
        prefill."""
        if self._prefills.pop(rid, None) is not None:
            self.stats.prefill_aborts += 1

    def prefill_emit_first(self, rid: int) -> None:
        """Emit the open prefill's first token (public wrapper for
        engines that sequence begin / compute / emit / insert themselves
        to keep the pool lock off the forward — no-op unless the state
        was opened with ``emit_first`` and hasn't emitted yet)."""
        self._prefill_emit(self._prefills[rid])

    # -- monolithic convenience wrappers (engine short-prompt path) -----

    def prefill_compute(self, view: DecodeRequestView, *,
                        emit_first: bool, reused_tokens: int = 0,
                        pool=None) -> Optional[Tuple]:
        """Phase 1 of a whole-prompt prefill: one bucketed chunk over the
        full history (same bit-exact forward, O(log^2) jit variants) plus
        the first-token emit.  With ``reused_tokens``/``pool`` the carry
        is seeded from the pool's restored reuse prefix and only the tail
        chunk is computed (see ``prefill_begin`` — the caller must hold
        the pool lock for the seed gather; single-threaded callers can
        ignore that).  Without a seed this touches NO pool state, so the
        engine runs it OUTSIDE the pool lock.  Returns the staged
        (k, v, blocks) for ``prefill_insert``."""
        total = self.prefill_begin(view, emit_first=emit_first,
                                   reused_tokens=reused_tokens, pool=pool)
        staged = self.prefill_chunk_compute(view.rid, total)
        self._prefill_emit(self._prefills[view.rid])
        return staged

    def prefill_insert(self, view: DecodeRequestView, pool, staged):
        """Phase 2: scatter the staged KV into the DONATED pool and
        register the row in the persistent device tables.  Run under the
        pool lock; returns the new pool — the caller must rebind its
        reference."""
        pool = self.prefill_chunk_insert(view.rid, pool, staged)
        self._prefills.pop(view.rid, None)
        self.stats.prefills += 1
        with self._trace.span("decode.rows", view.rid):
            self._register(view)
        return pool

    def prefill(self, view: DecodeRequestView, pool, *,
                emit_first: bool, reused_tokens: int = 0):
        """Convenience: both prefill phases back to back (single-threaded
        callers — tests, benchmarks).  The pool is DONATED (the seed
        gather, if any, reads it before the donating insert)."""
        staged = self.prefill_compute(view, emit_first=emit_first,
                                      reused_tokens=reused_tokens,
                                      pool=pool if reused_tokens else None)
        return self.prefill_insert(view, pool, staged)

    # ------------------------------------------------------------------

    @staticmethod
    def jit_cache_size() -> int:
        """Compiled-variant count of the decode step, single-device and
        sharded variants combined (all shapes/configs in this process) —
        the recompile metric for decode_hotpath."""
        return int(paged_decode_step_device._cache_size()
                   + paged_decode_step_device_sharded._cache_size())
