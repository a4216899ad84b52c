"""The served path against the float32 reference at Mistral-Nemo's head
layout, CPU only: q heads times head_dim wider than the residual (8 x 16
against 64), a GQA group of 4, an untied head and no q/k/v bias.

``ServingEngine`` serves two greedy requests in real mode on seeded
random weights: prefill, then decode through the paged pool, with the
first request swapped out to the host mid-decode and back in.  Every
served token is scored by ``bench/correct.py``'s comparison: its
reference logit below the reference's best, teacher-forced over the
prompt and the served tokens."""
import jax
import numpy as np
import pytest

from bench import correct
from bench.model import load_config, make_params, program_config
from bench.spec import BENCH_DIR

NEMO_TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                 num_attention_heads=8, num_key_value_heads=2, head_dim=16,
                 vocab_size=256)
# widest gap a sound run may read: the program's bfloat16 activations and
# KV against the float32 reference read at most 0.0043 over five seeds
# here, the float8 control at least 0.0415 (CPU, this size)
TOL = 0.015
PROMPTS = (37, 21)
MAX_TOKENS = 24
SWAP_AFTER = 8          # tokens of the first request before its swap-out


@pytest.fixture(scope="module")
def hp():
    hp = load_config(BENCH_DIR / "configs" / "mistral-nemo-12b-d4.json")
    hp.update(NEMO_TINY)
    assert hp["num_attention_heads"] * hp["head_dim"] != hp["hidden_size"]
    assert hp["num_attention_heads"] // hp["num_key_value_heads"] == 4
    assert not hp["tie_word_embeddings"] and not hp["qkv_bias"]
    return hp


def serve(hp, params, seed):
    """The two requests' teacher-forcing sequences, and the engine's
    swap counts."""
    from repro.core import EngineConfig, SamplingParams, ServingEngine
    from repro.core.serving import ReqState
    from repro.data.priority import PriorityTrace
    cfg = EngineConfig(mode="real", num_gpu_blocks=16, num_cpu_blocks=32,
                       block_size=16, max_running=2, max_batch=2,
                       seed=seed % 2 ** 31).with_policy("fastswitch")
    eng = ServingEngine(cfg, trace=PriorityTrace("markov", 1e-9),
                        model_bundle={"cfg": program_config(hp),
                                      "params": params},
                        stream_tokens=True, device=jax.devices("cpu")[0])
    rng = np.random.default_rng(seed)
    prompts = [[int(x) for x in rng.integers(1, hp["vocab_size"], n)]
               for n in PROMPTS]
    handles = [eng.add_request(p, SamplingParams(max_tokens=MAX_TOKENS,
                                                 temperature=0.0))
               for p in prompts]
    served = {h: [] for h in handles}
    done, swapped = set(), False
    while len(done) < len(handles):
        for o in eng.step():
            served[o.handle] += list(o.token_ids or [])
            if o.finished:
                done.add(o.handle)
        if not swapped and len(served[handles[0]]) >= SWAP_AFTER:
            eng._preempt(handles[0])
            assert eng._req(handles[0]).state == ReqState.SWAPPED
            swapped = True
    swaps_in = eng.metrics.swap_in_count
    eng.shutdown()
    seqs = []
    for h, p in zip(handles, prompts):
        seq = p + served[h]
        seqs.append({"seq": seq, "pos": list(range(len(p) - 1, len(seq) - 1)),
                     "served": served[h]})
    return seqs, swaps_in


@pytest.mark.parametrize("seed", [2, 2 ** 31 + 7])
def test_served_logits_match_the_reference_and_float8_does_not(hp, seed):
    params = make_params(hp, seed)
    seqs, swaps_in = serve(hp, params, seed)
    assert swaps_in == 1
    assert [len(s["served"]) for s in seqs] == [MAX_TOKENS] * len(PROMPTS)
    gap, n = correct.served_gap(params, hp, seqs)
    assert n == MAX_TOKENS * len(PROMPTS)
    assert gap <= TOL, gap
    assert correct.control_gap(params, hp, seqs) > TOL
