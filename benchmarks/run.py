"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Modules:
  tile_quantization      Fig. 1   (tile/block-policy FLOP overhead)
  precision_scaling      Fig. 3   (speedup over baseline precision)
  clock_sampling         Table I  (scrape-interval noise)
  prediction_accuracy    Table II / Fig. 4 (OFU vs Adjusted OFU accuracy)
  production_correlation Fig. 5 / Table III / SecV-C (608-job fleet)
  operational            Fig. 6 / Fig. 7 / SecVI-C (case studies)
  roofline               assigned-arch roofline table (needs dry-run JSONs)
"""
import sys
import traceback


def main() -> None:
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (clock_sampling, operational, precision_scaling,
                            prediction_accuracy, production_correlation,
                            roofline, tile_quantization)
    mods = [tile_quantization, precision_scaling, clock_sampling,
            prediction_accuracy, production_correlation, operational,
            roofline]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failures = 0
    for mod in mods:
        name = mod.__name__.split(".")[-1]
        if only and only != name:
            continue
        try:
            for row in mod.run():
                print(row.csv())
        except Exception as e:
            failures += 1
            print(f"{name},0,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark modules failed")


if __name__ == '__main__':
    main()
