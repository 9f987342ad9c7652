"""Benchmark harness — one function per paper table/figure plus kernel
benches.  Prints ``name,us_per_call,derived`` CSV (the contract used by
EXPERIMENTS.md).

  PYTHONPATH=src python -m benchmarks.run [--only substr]
"""
import argparse
import sys
import time

from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    enable_compile_cache()

    from benchmarks import paper_tables, kernel_bench, mc_bench

    benches = (list(paper_tables.ALL) + list(kernel_bench.ALL)
               + list(mc_bench.ALL))
    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    for fn in benches:
        if args.only and args.only not in fn.__name__:
            continue
        try:
            for name, us, derived in fn():
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"{fn.__name__},ERROR,{type(e).__name__}:{e}",
                  file=sys.stderr, flush=True)
    mc_bench.finalize_obs(failures=failures)
    print(f"# total {time.time()-t0:.1f}s, {failures} failures",
          file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
