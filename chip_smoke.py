"""Bring-up smoke: serve Qwen2-1.5B at its published widths on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four one-chip replicas + router

One process touches the chip and starts no other.  The weights are
random, made from ``--seed``.  The script drives the paths a user takes:
``ServingEngine.add_request`` / ``step`` / ``continue_session`` over the
paged pool, the ``DecodeRunner`` with the Pallas paged-attention decode,
and the staged swap plane.  Eight two-turn sessions oversubscribe the
device pool, so turns are swapped out and back in, and every second turn
reuses its session's KV.  The script then checks:

* every turn finishes (``length``/``stop``), with no request fault,
  no swap-copy failure or retry and no recompute fallback;
* swaps happened both ways, and the decode step holds a Mosaic kernel;
* every served token equals the argmax of a float32 reference
  (``forward_seq`` at ``highest`` matmul precision, teacher-forced over
  the served tokens) wherever that reference's top-1/top-2 logit margin
  exceeds ``MARGIN``;
* staged swaps move bytes exactly.

With ``--four-chips`` it runs only the replica phase: four engines, one
per device, behind ``FrontendServer`` and its router, driven over
loopback, compared with the same requests on one replica.

It exits non-zero, printing no result, unless JAX's first device is a
TPU.  The last line of its output is the JSON result.
"""
from __future__ import annotations

import argparse
import asyncio
import functools
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-1.5b"
N_SESSIONS = 8
# turn-1 prompts in (512, 1024] and turn-2 prompts in (1024, 2048]:
# two prefill buckets, so the cold compile stays short
PROMPT_TOKENS = ((520, 1000), (1100, 2000))
OUTPUT_TOKENS = (64, 128)
BLOCK_SIZE = 16
GPU_BLOCKS = 480                 # 7,680 tokens: the mix needs ~2.6x that
CPU_BLOCKS = 4096
# A served token may differ from the reference's argmax only where the
# reference's top two logits are closer than this (logit units).
MARGIN = 0.25
REF_TOKENS = 4096                # reference sequences are padded to this
REF_POSITIONS = 512              # checked positions per session, padded
PRIORITY_UPDATE_FREQ = 0.05


def log(msg: str) -> None:
    print(msg, flush=True)


def make_sessions(seed: int, vocab: int):
    """``N_SESSIONS`` two-turn conversations of random token ids."""
    rng = np.random.default_rng(seed)
    sessions = []
    for _ in range(N_SESSIONS):
        prompts = [rng.integers(0, vocab, int(rng.integers(lo, hi + 1)))
                   .tolist() for lo, hi in PROMPT_TOKENS]
        outs = [int(x) for x in rng.integers(OUTPUT_TOKENS[0],
                                             OUTPUT_TOKENS[1] + 1, 2)]
        sessions.append({"prompts": prompts, "max_tokens": outs})
    return sessions


def engine_config():
    from repro.core import EngineConfig
    return EngineConfig(mode="real", num_gpu_blocks=GPU_BLOCKS,
                        num_cpu_blocks=CPU_BLOCKS, block_size=BLOCK_SIZE,
                        max_running=N_SESSIONS,
                        max_batch=N_SESSIONS).with_policy("fastswitch")


def oversubscription(sessions) -> float:
    """Tokens the sessions hold at the end of their second turns, over
    the device pool's token capacity."""
    need = sum(len(s["prompts"][0]) + len(s["prompts"][1])
               + sum(s["max_tokens"]) for s in sessions)
    return need / (GPU_BLOCKS * BLOCK_SIZE)


def serve(engine, sessions):
    """Drive ``engine`` through both turns of every session.  Returns
    ({handle: [turn-1 tokens, turn-2 tokens]}, {(handle, turn): reason})."""
    from repro.core import SamplingParams
    tokens = {h: [[], []] for h in range(len(sessions))}
    reasons = {}
    for h, s in enumerate(sessions):
        engine.add_request(s["prompts"][0],
                           SamplingParams(max_tokens=s["max_tokens"][0]),
                           handle=h, retain_kv=True)
    while engine.has_work():
        for out in engine.step():
            if out.token_ids:
                tokens[out.handle][out.turn].extend(out.token_ids)
            if not out.finished:
                continue
            reasons[(out.handle, out.turn)] = out.finish_reason
            if out.turn == 0 and out.finish_reason in ("length", "stop"):
                s = sessions[out.handle]
                engine.continue_session(
                    out.handle, s["prompts"][1],
                    SamplingParams(max_tokens=s["max_tokens"][1]))
    return tokens, reasons


# ---------------------------------------------------------------------------
# float32 reference
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def _reference(params, tokens, positions, served, *, cfg):
    """Teacher-forced float32 forward over ``tokens`` (1, REF_TOKENS).
    At each of ``positions``: the top-2 logits, the argmax and the logit
    of the ``served`` next token; also the first position's logit row."""
    from repro.models import transformer as T
    x, _, _ = T.forward_seq(params, cfg, tokens, return_hidden=True,
                            compute_dtype=jnp.float32)
    h = x[0, positions]                                  # (P, d)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = h @ head["table"].T                         # (P, V) float32
    top, idx = jax.lax.top_k(logits, 2)
    served_logit = jnp.take_along_axis(logits, served[:, None], axis=1)[:, 0]
    return top, idx[:, 0], served_logit, logits[0]


def reference(bundle, seq, positions):
    """The float32 reference at ``positions`` of ``seq``, each predicting
    ``seq[t + 1]``: (top-1/top-2 margin, agreement of the argmax with
    the next token, how far the next token's logit is below the top,
    the logit row at ``positions[0]``)."""
    n = len(positions)
    assert len(seq) <= REF_TOKENS and n <= REF_POSITIONS, (len(seq), n)
    tok = np.zeros((1, REF_TOKENS), np.int32)
    tok[0, :len(seq)] = seq
    pos = np.zeros((REF_POSITIONS,), np.int32)
    pos[:n] = positions
    nxt = np.zeros((REF_POSITIONS,), np.int32)
    nxt[:n] = [seq[t + 1] for t in positions]
    with jax.default_matmul_precision("highest"):
        top, arg, served, row0 = jax.device_get(_reference(
            bundle["params"], tok, pos, nxt, cfg=bundle["cfg"]))
    return (top[:n, 0] - top[:n, 1], arg[:n] == nxt[:n],
            top[:n, 0] - served[:n], row0)


def reference_check(bundle, session, toks):
    """Check one session's served tokens against the float32 reference.
    Returns (positions checked, mismatches among them, the largest
    deficit of a served token below the reference's top logit, the
    reference logit row at the last turn-1 prompt position)."""
    (p1, p2), (g1, g2) = session["prompts"], toks
    seq = p1 + g1 + p2 + g2
    # generated token j of a turn starting at index s is predicted at s-1+j
    starts = (len(p1), len(p1) + len(g1) + len(p2))
    pos = [s - 1 + j for s, g in zip(starts, (g1, g2)) for j in range(len(g))]
    margin, agree, deficit, row0 = reference(bundle, seq, pos)
    checked = margin > MARGIN
    worst = float(np.max(deficit[~agree])) if np.any(~agree) else 0.0
    return int(np.sum(checked)), int(np.sum(checked & ~agree)), worst, row0


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def one_chip(bundle, seed: int) -> None:
    from repro.core import ServingEngine
    from repro.data.priority import PriorityTrace
    from repro.kernels import ops
    from repro.models.paged import paged_decode_step_device

    cfg = bundle["cfg"]
    dev = jax.devices()[0]
    sessions = make_sessions(seed, cfg.vocab_size)
    log(f"traffic: {N_SESSIONS} sessions x 2 turns, prompts "
        f"{[len(p) for s in sessions for p in s['prompts']]}, outputs "
        f"{[m for s in sessions for m in s['max_tokens']]}; "
        f"pool {GPU_BLOCKS} blocks x {BLOCK_SIZE} tokens, oversubscribed "
        f"{oversubscription(sessions):.2f}x")
    engine = ServingEngine(
        engine_config(),
        trace=PriorityTrace("markov", PRIORITY_UPDATE_FREQ, seed=seed),
        model_bundle=bundle, stream_tokens=True, device=dev)
    pool_bytes = engine.pools.gpu.nbytes
    log(f"engine: policy fastswitch, cost model "
        f"{engine.iter_cost.hw.name}, pool {pool_bytes} bytes on {dev}")

    t0 = time.perf_counter()
    tokens, reasons = serve(engine, sessions)
    jax.block_until_ready(engine.pools.gpu)
    serve_s = time.perf_counter() - t0
    engine.shutdown()
    m = engine.metrics
    sw = engine.swap.stats()
    log(f"served {m.total_tokens} tokens in {m.iterations} steps, "
        f"{serve_s:.3f} s wall (compiles included)")
    log("swaps: " + json.dumps({
        "swap_out": m.swap_out_count, "swap_in": m.swap_in_count,
        "preemptions": m.preemptions, "ops_out": sw["ops_out"],
        "ops_in": sw["ops_in"], "blocks_out": sw["blocks_out"],
        "blocks_in": sw["blocks_in"], "copy_retries": sw["copy_retries"],
        "copy_failures": sw["copy_failures"],
        "swap_failure_resumes": m.swap_failure_resumes,
        "faulted": m.faulted}))
    # second turns reuse their session's KV: prefill forwards roughly
    # the new prompts, not the whole histories
    history = sum(2 * len(s["prompts"][0]) + s["max_tokens"][0]
                  + len(s["prompts"][1]) for s in sessions)
    forwarded = engine.runner.stats.prefill_tokens
    log(f"prefill forwarded {forwarded} tokens; without KV reuse "
        f"{history}")
    swapped = sorted({e.handle for e in engine.events
                      if e.kind == "preempt"
                      and e.data.get("to") == "swapped"})
    log(f"sessions swapped out mid-turn and back in: {swapped}")

    bad = {k: r for k, r in reasons.items() if r not in ("length", "stop")}
    assert len(reasons) == 2 * N_SESSIONS and not bad, (reasons, bad)
    for h, s in enumerate(sessions):
        for t in range(2):
            assert len(tokens[h][t]) == s["max_tokens"][t] or \
                reasons[(h, t)] == "stop", (h, t, len(tokens[h][t]))
    assert m.faulted == 0, m.faulted
    assert sw["copy_failures"] == 0 and sw["copy_retries"] == 0, sw
    assert m.swap_failure_resumes == 0, m.swap_failure_resumes
    assert m.swap_out_count > 0 and m.swap_in_count > 0, \
        (m.swap_out_count, m.swap_in_count)
    assert swapped, "no session was swapped out mid-turn"
    assert forwarded < history, (forwarded, history)

    # the decode step as served holds the Pallas kernel
    r = engine.runner
    step_args = (r._params, engine.pools.gpu, r._bt, r._ctx, r._tok,
                 r._active, r._keys, r._sampling)
    hlo = paged_decode_step_device.lower(
        *step_args, cfg=cfg).compile().as_text()
    assert "tpu_custom_call" in hlo, "decode step has no Mosaic kernel"
    log(f"decode step (batch {r._bt.shape[0]}, pages {r._bt.shape[1]}): "
        "tpu_custom_call in compiled HLO")

    # reference check over every session
    n_checked = n_mism = 0
    worst = 0.0
    logit_err = 0.0
    t0 = time.perf_counter()
    for h, s in enumerate(sessions):
        c, mm, w, row0 = reference_check(bundle, s, tokens[h])
        n_checked, n_mism, worst = n_checked + c, n_mism + mm, max(worst, w)
        # the served prefill's own logits at the turn-1 prompt's end
        served_row, *_ = ops.prefill_chunk(
            r._params, s["prompts"][0], None, None, 0, cfg=cfg,
            block_size=BLOCK_SIZE)
        logit_err = max(logit_err, float(np.max(np.abs(
            np.asarray(served_row) - row0))))
    log(f"reference (float32, highest precision): {n_checked} positions "
        f"with top-1/top-2 margin > {MARGIN} checked, {n_mism} "
        f"mismatches; largest reference-logit deficit of a served token "
        f"{worst:.4f}; largest served-prefill logit error {logit_err:.4f} "
        f"({time.perf_counter() - t0:.1f} s)")
    assert n_mism == 0, n_mism
    assert n_checked >= 32, n_checked

    # data movement is exact: a staged round trip returns the same bytes
    pools = engine.pools
    third = (GPU_BLOCKS - 1) // 3                  # the last block is trash
    src_runs, n = [(0, 8), (third, 5)], 13
    before = np.asarray(pools.gpu[:, :, np.r_[0:8, third:third + 5]]
                        ).view(np.uint16)
    cpu_blocks = list(range(CPU_BLOCKS - n, CPU_BLOCKS))
    pools.copy_out_staged(src_runs, cpu_blocks)
    pools.copy_in_staged(cpu_blocks, [(2 * third, n)])
    after = np.asarray(pools.gpu[:, :, 2 * third:2 * third + n]
                       ).view(np.uint16)
    assert np.any(before != 0), "round-trip source blocks are empty"
    assert np.array_equal(pools.cpu[:, :, cpu_blocks], before)
    assert np.array_equal(after, before)
    log(f"staged swap round trip of {n} blocks: bytes identical")

    # warm wall-clock times of the served step programs
    t0 = time.perf_counter()
    out = ops.prefill_chunk(r._params, sessions[0]["prompts"][0], None,
                            None, 0, cfg=cfg, block_size=BLOCK_SIZE)
    jax.block_until_ready(out)
    prefill_s = time.perf_counter() - t0
    pool, bt, ctx, tok = pools.gpu, r._bt, r._ctx, r._tok
    n_steps = 8
    t0 = time.perf_counter()
    for _ in range(n_steps):
        _, pool, ctx, tok = paged_decode_step_device(
            r._params, pool, bt, ctx, tok, r._active, r._keys,
            r._sampling, cfg=cfg)
    jax.block_until_ready(pool)
    decode_s = (time.perf_counter() - t0) / n_steps
    log(f"wall clock (warm, block_until_ready): prefill of "
        f"{len(sessions[0]['prompts'][0])} tokens {prefill_s * 1e3:.2f} "
        f"ms; decode step at batch {bt.shape[0]}, pages {bt.shape[1]} "
        f"{decode_s * 1e3:.3f} ms")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


async def _client(host, port, name, session):
    """One socket client: both turns of one session.  Returns
    ([turn-1 tokens, turn-2 tokens], finish reasons, replicas)."""
    reader, writer = await asyncio.open_connection(host, port)
    toks, reasons, replicas = [[], []], [], []

    def send(obj):
        writer.write(json.dumps(obj).encode() + b"\n")

    send({"op": "submit", "id": name, "client": name,
          "prompt": session["prompts"][0],
          "max_tokens": session["max_tokens"][0], "retain": True})
    await writer.drain()
    while len(reasons) < 2:
        line = await reader.readline()
        if not line:
            raise ConnectionError(f"{name}: server closed the connection")
        ev = json.loads(line)
        kind = ev.get("event")
        if kind == "accepted":
            replicas.append(ev["replica"])
        elif kind == "token":
            toks[len(reasons)].extend(ev.get("token_ids") or [])
        elif kind == "finish":
            reasons.append(ev["reason"])
            if len(reasons) == 1 and ev.get("retained"):
                send({"op": "continue", "handle": ev["handle"],
                      "prompt": session["prompts"][1],
                      "max_tokens": session["max_tokens"][1],
                      "retain": False})
                await writer.drain()
            elif len(reasons) == 1:
                break
        elif kind == "error":
            raise RuntimeError(f"{name}: server error {ev}")
    writer.close()
    await writer.wait_closed()
    return toks, reasons, replicas


async def _drive(engines, sessions):
    from repro.frontend.server import FrontendServer
    srv = FrontendServer(engines, admission_capacity=64)
    host, port = await srv.start()
    try:
        return await asyncio.gather(*[
            _client(host, port, f"s{i}", s) for i, s in enumerate(sessions)])
    finally:
        await srv.close()


def four_chips(bundle, seed: int) -> None:
    from repro.core import ServingEngine
    from repro.data.priority import PriorityTrace
    from repro.frontend.router import count_affinity_violations
    from repro.launch.serve import replica_devices

    sessions = make_sessions(seed, bundle["cfg"].vocab_size)
    devices = replica_devices(4)
    results = {}
    for name, devs in (("4 replicas", devices), ("1 replica", devices[:1])):
        engines = [ServingEngine(
            engine_config(),
            trace=PriorityTrace("markov", PRIORITY_UPDATE_FREQ, seed=seed),
            model_bundle=bundle, stream_tokens=True, device=d)
            for d in devs]
        t0 = time.perf_counter()
        res = asyncio.run(_drive(engines, sessions))
        wall = time.perf_counter() - t0
        violations = count_affinity_violations(
            [[ev.as_dict() for ev in e.events] for e in engines])
        log(f"{name}: {wall:.3f} s wall (compiles included), sessions per "
            f"replica {[sum(r[0] == i for _, _, r in res) for i in range(len(devs))]}"
            f", affinity violations {violations}, finish reasons "
            f"{sorted({r for _, rs, _ in res for r in rs})}")
        for i, e in enumerate(engines):
            in_use = (e.device.memory_stats() or {}).get("bytes_in_use", 0)
            params_bytes = sum(x.nbytes for x in jax.tree.leaves(
                e.runner._params))
            log(f"  replica {i} on {e.device}: {in_use} bytes in use, "
                f"params {params_bytes}, pool {e.pools.gpu.nbytes}, swaps "
                f"out/in {e.metrics.swap_out_count}/{e.metrics.swap_in_count}"
                f", faulted {e.metrics.faulted}")
            assert e.device == devs[i] and e.metrics.faulted == 0
            assert in_use >= params_bytes + e.pools.gpu.nbytes, in_use
            e.shutdown()
        assert violations == 0, violations
        assert all(len(rs) == 2 and set(rs) <= {"length", "stop"}
                   for _, rs, _ in res), res
        results[name] = [t for t, _, _ in res]

    # Tokens agree with the one-replica run, or first diverge where the
    # reference (on the one-replica sequence) has a near-tie: the margin
    # rule of the one-chip check.  After that the continuations differ.
    n_same = 0
    for h, s in enumerate(sessions):
        a, b = results["4 replicas"][h], results["1 replica"][h]
        if a == b:
            n_same += 1
            continue
        t = 0 if a[0] != b[0] else 1
        j = next(i for i, (x, y) in enumerate(zip(a[t], b[t])) if x != y)
        seq = s["prompts"][0] + (b[0][:j + 1] if t == 0 else
                                 b[0] + s["prompts"][1] + b[1][:j + 1])
        margin, _, _, _ = reference(bundle, seq, [len(seq) - 2])
        log(f"  session {h} turn {t + 1} diverges at token {j}: "
            f"reference margin {float(margin[0]):.4f}")
        assert margin[0] <= MARGIN, (h, t, j, margin)
    log(f"tokens vs one replica: {n_same} of {len(sessions)} sessions "
        f"identical, the rest diverge first at a reference near-tie")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica router phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{devices[0].platform}", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import _build_real_bundle
    log(f"device: {devices[0].device_kind} x {len(devices)}, compile "
        f"cache {enable_compile_cache()}")
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(secs)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    t0 = time.perf_counter()
    bundle = _build_real_bundle(ARCH, args.seed)
    jax.block_until_ready(bundle["params"])
    log(f"params made in {time.perf_counter() - t0:.1f} s")

    if args.four_chips:
        four_chips(bundle, args.seed)
    else:
        one_chip(bundle, args.seed)
    for i, d in enumerate(devices[:need]):
        stats = d.memory_stats() or {}
        log(f"device {i} peak memory {stats.get('peak_bytes_in_use')} of "
            f"{stats.get('bytes_limit')} bytes")
    log(f"compiles: {len(compiles)}, {sum(compiles):.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": need}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
