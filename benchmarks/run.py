"""Benchmark harness — one module per paper table/figure (DESIGN.md §7).

Prints a ``name,value,derived`` CSV summary at the end. Run:
    PYTHONPATH=src python -m benchmarks.run [--only tableN]
"""
import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark module name")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (design_space, kernel_bench, numerics_bench,
                            serve_bench, table1_narrow_fp, table2_image_cls,
                            table3_lstm_lm, throughput_model)
    suites = [
        ("table1_narrow_fp", table1_narrow_fp),
        ("table2_image_cls", table2_image_cls),
        ("table3_lstm_lm", table3_lstm_lm),
        ("design_space", design_space),
        ("throughput_model", throughput_model),
        ("kernel_bench", kernel_bench),
        ("numerics_overhead", numerics_bench),
        ("serve_traffic", serve_bench),
    ]
    csv = ["name,value,derived"]
    for name, mod in suites:
        if args.only and args.only not in name:
            continue
        print(f"\n==== {name} ====", flush=True)
        t0 = time.time()
        rows = mod.run()
        dt = time.time() - t0
        print(f"({name}: {dt:.1f}s)")
        for r in rows:
            vals = ",".join(f"{v:.6g}" if isinstance(v, float) else str(v)
                            for v in r[1:])
            csv.append(f"{name}/{r[0]},{vals}")
    print("\n==== CSV summary ====")
    print("\n".join(csv))


if __name__ == "__main__":
    main()
