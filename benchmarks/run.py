"""Benchmark harness — one module per paper table/figure.

  loc_table         Tables 1-2: engineering cost in LoC
  overhead          Fig. 8:     scheduling/dispatch CPU overhead
  throughput_model  Figs 9-12:  modeled strategy gains from real plans
  ablation          Fig. 14:    memory / graph / dynamic ablation
  sensitivity       Fig. 2a:    batch-size split sensitivity

Prints ``name,value,unit`` CSV lines.  Dry-run-derived rooflines live in
results/dryrun/*.json (written by repro.launch.dryrun).
"""
import sys
import time


def main() -> None:
    from benchmarks import ablation, loc_table, overhead, report, \
        sensitivity, throughput_model
    from repro.launch.jax_cache import use_compile_cache
    use_compile_cache()
    for mod in (loc_table, overhead, throughput_model, ablation,
                sensitivity, report):
        name = mod.__name__.split(".")[-1]
        print(f"# === {name} ===", flush=True)
        t0 = time.perf_counter()
        try:
            for line in mod.run():
                print(line, flush=True)
        except Exception as e:
            print(f"{name},ERROR,{type(e).__name__}: {e}", flush=True)
        print(f"# {name} took {time.perf_counter() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
