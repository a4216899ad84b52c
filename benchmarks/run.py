"""Benchmark harness entry point — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (spec'd format)."""
import sys
import time
import traceback

from benchmarks import (baselines_related_work, decode_hotpath,
                        fig1_latency_breakdown, fig2_waiting_requests,
                        fig8_slo_latency, fig8_throughput, fig9_callstack,
                        fig10_ctx_switch, fig11_sensitivity,
                        fig12_token_efficiency, fig13_cpu_memory,
                        kernel_microbench, roofline_report,
                        table1_microbench)

ALL = [
    ("fig1", fig1_latency_breakdown),
    ("fig2", fig2_waiting_requests),
    ("fig8_slo", fig8_slo_latency),
    ("fig8_throughput", fig8_throughput),
    ("fig9", fig9_callstack),
    ("fig10", fig10_ctx_switch),
    ("fig11", fig11_sensitivity),
    ("fig12", fig12_token_efficiency),
    ("fig13", fig13_cpu_memory),
    ("table1", table1_microbench),
    ("baselines", baselines_related_work),
    ("kernels", kernel_microbench),
    ("decode_hotpath", decode_hotpath),
    ("roofline", roofline_report),
]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = set(sys.argv[1:])
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in ALL:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            if name == "fig8_slo":
                # full 2-model x 2-pattern grid is the EXPERIMENTS.md run;
                # the default harness does the paper's primary scenario
                mod.main(scenarios=("llama8b-a10",),
                         patterns=("markov", "random"))
            else:
                mod.main()
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failures += 1
            print(f"# {name} FAILED:", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
