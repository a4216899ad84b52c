"""The correctness check at a size a CPU run holds: a sound run of each
mix comes out correct, the precision control and each fault the cells
can have come out not correct.  The harness runs as ``run.py`` runs it,
less the look for a chip, with the compile cache off."""
import time

import jax
import pytest

from bench import correct, faults, harness
from bench.model import load_config
from bench.spec import BENCH_DIR, REPO, Cell, load_json, load_mix

TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=256)
# widest gap a sound tiny run may read (CPU, 2 layers, width 64): sound
# runs read about 0.003, the float8 control about 0.05
LIMIT = 0.015

SMALL_MIX = {
    "chat-switch": dict(max_context=96, rate_per_s=1.0, session_s=4,
                        prompt_mu=2.0, response_mu=1.5,
                        max_tokens=30, warmup_s=3.0, think_mean_s=0.2,
                        think_sd_s=0.1, think_min_s=0.1, check_tokens=200),
    "bulk": dict(clients=4, population=32, prompt_mu=3.0, prompt_cap=48,
                 output_min=8, output_max=24, max_context=72, warmup_s=1.0,
                 check_tokens=200),
}
SMALL_ENGINE = {
    "chat-switch": dict(num_gpu_blocks=12, num_cpu_blocks=128,
                        max_running=4),
    "bulk": dict(num_gpu_blocks=32, num_cpu_blocks=128, max_running=4),
}


# the configuration each mix runs on (the bulk mix's cell is not in
# BENCHMARK.json today; its files are kept for a later one)
CONFIG = {"chat-switch": "qwen2-1.5b", "bulk": "mistral-nemo-12b-d4"}


def tiny_cell(traffic: str) -> Cell:
    bm = load_json(REPO / "BENCHMARK.json")
    name = f"{CONFIG[traffic]}.{traffic}"
    hp = load_config(BENCH_DIR / "configs" / f"{CONFIG[traffic]}.json")
    hp.update(TINY)
    mix = load_mix(traffic)
    mix.update(SMALL_MIX[traffic])
    mix["engine"].update(SMALL_ENGINE[traffic])
    if traffic == "chat-switch":
        mix["engine"]["priority"]["update_freq"] = 0.1
    rep = [m for m in bm["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    return Cell(name=name, chips=1, config=hp, mix=mix,
                limits={"max_logit_gap": {"limit": LIMIT}},
                end_to_end=rep, per_layer=[],
                peaks={"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11})


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off")


# window seconds: enough steps of the interpreted kernels on a loaded CPU
# for follow-up turns on restored KV to finish
SECONDS = {"chat-switch": 8.0, "bulk": 4.0}


def run(cell, seed=2 ** 31 + 7):
    return harness.run(cell, seed, SECONDS[cell.mix["name"]], False,
                       time.perf_counter(), devices=jax.devices("cpu"))


@pytest.mark.parametrize("traffic", ["chat-switch", "bulk"])
def test_a_sound_run_is_correct_and_the_control_is_not(traffic,
                                                       monkeypatch):
    cell = tiny_cell(traffic)
    seen = {}
    check = correct.check

    def keep(cell, params, seqs, seed):
        seen.update(params=params, seqs=seqs, seed=seed)
        return check(cell, params, seqs, seed)

    monkeypatch.setattr(correct, "check", keep)
    res = run(cell)
    assert res["correct"], res["compared"]
    assert res["compared"]["tokens_compared"]["value"] > 20
    picked = correct.sample(seen["seqs"], seen["seed"], 200)
    ctrl = correct.control_gap(seen["params"], cell.config, picked)
    assert ctrl > LIMIT, ctrl


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_broken_decode_step_is_not_correct(fault, monkeypatch):
    import repro.core.decode_runner as dr
    monkeypatch.setattr(dr, "paged_decode_step_device",
                        dr.paged_decode_step_device)
    faults.plant(fault)
    res = run(tiny_cell("chat-switch"))
    assert not res["correct"], res["compared"]
    assert res["compared"]["max_logit_gap"]["value"] > LIMIT
