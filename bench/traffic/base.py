"""What a generator hands the harness's client, and the helpers the
generators share."""
from __future__ import annotations

import math
import random
from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class Submit:
    """One turn to send: the first turn of session ``sid`` goes through
    ``add_request``, a later one through ``continue_session``."""
    sid: int
    turn: int
    prompt: List[int]
    max_tokens: int
    retain: bool          # keep the KV for a further turn
    due: float            # seconds after the traffic's start


def lognormal_len(rng: random.Random, mu: float, sigma: float, cap: int,
                  floor: int = 4) -> int:
    return int(min(cap, max(floor, rng.lognormvariate(mu, sigma))))


def geometric(rng: random.Random, p: float) -> int:
    """Failures before the first success."""
    u = rng.random()
    return int(math.floor(math.log(max(u, 1e-12))
                          / math.log(max(1 - p, 1e-12))))


def token_ids(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(1, vocab, n).tolist()


def permuted(items: list, rng: np.random.Generator) -> list:
    return [items[i] for i in rng.permutation(len(items))]


def pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def fresh_prefills(max_prompt: int, block_size: int):
    """Prefill buckets of a prompt with nothing cached: (chunk, 0, 0) for
    every pow2 chunk bucket a prompt of up to ``max_prompt`` tokens
    takes."""
    c, out = block_size, []
    while c <= pow2(max_prompt):
        out.append((c, 0, 0))
        c *= 2
    return out


def reused_prefills(max_context: int, block_size: int):
    """Prefill buckets of a follow-up whose first ``prefix`` tokens are
    cached: one (chunk, prefix, prefix) per distinct (chunk bucket, seeded
    carry bucket, final carry bucket), with ``prefix`` block-aligned and
    room for a chunk that fills more than half its bucket."""
    seen, out = set(), []
    c = block_size
    while c <= pow2(max_context):
        least = 1 if c == block_size else c // 2 + 1
        for prefix in range(block_size, max_context - least + 1, block_size):
            seeded = pow2(prefix // block_size) * block_size
            final = max(seeded, pow2(prefix + c))
            if (c, seeded, final) not in seen:
                seen.add((c, seeded, final))
                out.append((c, prefix, prefix))
        c *= 2
    return out
