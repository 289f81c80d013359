"""Benchmark harness: one section per paper table.

    PYTHONPATH=src python -m benchmarks.run            # tables 1a, 1b, 2
    PYTHONPATH=src python -m benchmarks.run --roofline # + dry-run roofline

Prints ``name,us_per_call,derived`` CSV per table (derived = the paper's
metric for that table: Ops/Unit + unit counts, or manual-vs-auto parity).
"""
import argparse

from repro.launch import xla_setup


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--roofline", action="store_true",
                    help="also print the dry-run roofline table (requires "
                         "results/dryrun_baseline.json)")
    args = ap.parse_args()
    xla_setup.configure()

    from benchmarks import table1a, table1b, table2_cnn
    from benchmarks.common import print_rows

    print_rows(table1a.run(),
               "Table 1a: addition-intensive (paper: Ops/Unit -> ~3.3, "
               "~70% unit reduction)")
    print_rows(table1b.run(),
               "Table 1b: mul/MAD-intensive (paper: Ops/Unit -> ~2.0, "
               "~50% unit reduction)")
    table2_cnn.print_rows(
        table2_cnn.run(),
        "Table 2: CNN accelerators, manual (M) vs automatic (S) packing "
        "(paper: S == M)")

    if args.roofline:
        from benchmarks import roofline
        roofline.report()


if __name__ == "__main__":
    main()
