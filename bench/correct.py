"""Is what the timed path served correct?

Once the window has closed and the program's state is freed, a sample
of the finished sessions (drawn from the seed, with the session that
served the most tokens and, where there is one, a session that was
swapped out mid-turn and back in) is run through the plain float32
reference, teacher-forced over its prompts and served tokens.  At each
served token's position the number compared is how far that token's
reference logit lies below the reference's best logit.  Greedy decoding
at the configuration's precision leaves that gap at rounding size; the
widest gap over the sample is held to the cell's limit
(``limits/<workload>.json``).
"""
from __future__ import annotations

import functools
import importlib
import random
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.traffic.base import pow2

OK_REASONS = ("length", "stop")
POSITIONS = 256                     # reference positions per call


def sequences(turns: Dict[Tuple[int, int], Dict], resumed: set,
              midturn: set) -> List[Dict]:
    """Each session's completed turns as one teacher-forcing sequence:
    the prompts and served tokens in order, the positions that predict a
    served token, and those tokens.  ``resumed``: sessions whose KV came
    back by swap-in (``midturn``: swapped out in the middle of a turn)."""
    by_sid: Dict[int, List[Tuple[int, Dict]]] = {}
    for (sid, turn), t in turns.items():
        by_sid.setdefault(sid, []).append((turn, t))
    out = []
    for sid, ts in by_sid.items():
        seq: List[int] = []
        pos: List[int] = []
        served: List[int] = []
        done = 0
        for _, t in sorted(ts, key=lambda x: x[0]):
            if not (t["done"] and t["reason"] in OK_REASONS):
                break
            done += 1
            seq += t["prompt"]
            for tok in t["tokens"]:
                pos.append(len(seq) - 1)
                served.append(tok)
                seq.append(tok)
        if served:
            # a follow-up turn among those compared was admitted on KV
            # restored from the host
            out.append({"sid": sid, "seq": seq, "pos": pos,
                        "served": served,
                        "resumed": sid in resumed and done > 1,
                        "midturn": sid in midturn})
    return out


def sample(seqs: List[Dict], seed: int, target_tokens: int) -> List[Dict]:
    """The session with the most served tokens, a resumed one if any (one
    swapped out mid-turn where there is one), then others drawn from the
    seed until ``target_tokens`` served tokens."""
    if not seqs:
        return []
    rng = random.Random(seed)
    rest = sorted(seqs, key=lambda s: s["sid"])
    longest = max(rest, key=lambda s: (len(s["served"]), -s["sid"]))
    picked = [longest]
    rest.remove(longest)
    resumed = ([s for s in rest if s["midturn"]]
               or [s for s in rest if s["resumed"]])
    if resumed and not longest["midturn"]:
        r = rng.choice(resumed)
        picked.append(r)
        rest.remove(r)
    rng.shuffle(rest)
    n = sum(len(s["served"]) for s in picked)
    for s in rest:
        if n >= target_tokens:
            break
        picked.append(s)
        n += len(s["served"])
    return picked


@functools.lru_cache(maxsize=None)
def _programs(ref_name: str, hp_items: tuple, quant: Optional[str]):
    """Jitted hidden-state and scoring programs of the reference."""
    import jax
    import jax.numpy as jnp
    ref = importlib.import_module(f"bench.reference.{ref_name}")
    hp = dict(hp_items)

    @jax.jit
    def hidden(params, tokens):
        return ref.forward_hidden(params, hp, tokens, quant)

    @jax.jit
    def scores(params, h, positions, cand):
        lg = ref.logits_at(params, hp, h, positions, quant)
        top = jnp.max(lg, axis=-1)
        arg = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        c = jnp.take_along_axis(lg, cand[:, None], axis=-1)[:, 0]
        return top, arg, c
    return hidden, scores


def _hp_key(hp: Dict) -> tuple:
    return tuple(sorted((k, v) for k, v in hp.items()
                        if isinstance(v, (int, float, bool, str))))


def reference_scores(params, hp: Dict, s: Dict, cand: Optional[List[int]]
                     = None, quant: Optional[str] = None,
                     min_len: int = 1024):
    """At each of ``s["pos"]``: the reference's best logit, its argmax and
    the logit of ``cand`` (default the served tokens)."""
    hidden, scores = _programs(hp["reference"], _hp_key(hp), quant)
    seq = s["seq"]
    T = max(min_len, pow2(len(seq)))
    tok = np.zeros((T,), np.int32)
    tok[:len(seq)] = seq
    h = hidden(params, tok)
    cand = s["served"] if cand is None else cand
    tops, args, cs = [], [], []
    for i in range(0, len(s["pos"]), POSITIONS):
        p = np.zeros((POSITIONS,), np.int32)
        c = np.zeros((POSITIONS,), np.int32)
        n = min(POSITIONS, len(s["pos"]) - i)
        p[:n] = s["pos"][i:i + n]
        c[:n] = cand[i:i + n]
        top, arg, cv = (np.asarray(x) for x in scores(params, h, p, c))
        tops.append(top[:n])
        args.append(arg[:n])
        cs.append(cv[:n])
    return np.concatenate(tops), np.concatenate(args), np.concatenate(cs)


def served_gap(params, hp: Dict, picked: List[Dict]) -> Tuple[float, int]:
    """Widest gap of a served token's reference logit below the
    reference's best, over ``picked``; and the tokens compared."""
    import jax
    worst, n = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for s in picked:
            top, _, c = reference_scores(params, hp, s)
            worst = max(worst, float(np.max(top - c)))
            n += len(c)
    return worst, n


def control_gap(params, hp: Dict, picked: List[Dict]) -> float:
    """The same reading for the precision control: the reference run in
    float8, whose argmax at each position is scored by the float32
    reference."""
    import jax
    worst = 0.0
    with jax.default_matmul_precision("highest"):
        for s in picked:
            _, arg8, _ = reference_scores(params, hp, s, quant="fp8")
            top, _, c = reference_scores(params, hp, s, cand=list(arg8))
            worst = max(worst, float(np.max(top - c)))
    return worst


def check(cell, params, seqs: List[Dict], seed: int) -> Dict:
    """The verdict, with each number compared beside its limit (printed
    as the last lines of standard error)."""
    target = cell.mix.get("check_tokens", 600)
    picked = sample(seqs, seed, target)
    t = time.perf_counter()
    gap, n = served_gap(params, cell.config, picked) if picked else (None, 0)
    limit = cell.limits.get("max_logit_gap", {}).get("limit")
    # sessions whose KV went to the host and came back: a follow-up on
    # restored KV, or a turn swapped out mid-decode and back in
    back = sum(1 for s in picked if s["resumed"] or s["midturn"])
    need_back = cell.mix.get("check_resumed", False)
    ok = (gap is not None and limit is not None and gap <= limit
          and n > 0 and (back > 0 or not need_back))
    compared = {"max_logit_gap": {"value": gap, "limit": limit},
                "tokens_compared": {"value": n, "min": 1},
                "swapped_back_compared": {"value": back,
                                          "min": 1 if need_back else 0}}
    print(f"reference check: {len(picked)} sessions, {n} served tokens, "
          f"{time.perf_counter() - t:.1f} s", file=sys.stderr)
    for k, v in compared.items():
        bound = (f"limit {v['limit']}" if "limit" in v
                 else f"at least {v['min']}")
        print(f"compared {k}: {v['value']} ({bound})", file=sys.stderr,
              flush=True)
    return {"correct": bool(ok), "compared": compared}
