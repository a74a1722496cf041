"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Select subsets with
``python -m benchmarks.run fig8 table6 ...`` (default: all).
"""
from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import (
        fig8_fastest,
        fig8_scaling,
        fig9_partition,
        fig10_theory,
        fig11_stagewise,
        roofline_table,
        serve_load,
        spin_scaling,
        strassen_hlo,
        table6_single_node,
        table7_leaf,
    )

    suites = {
        "fig8": fig8_fastest.run,
        "fig8_scaling": fig8_scaling.run,
        "table6": table6_single_node.run,
        "table7": table7_leaf.run,
        "fig9": fig9_partition.run,
        "fig10": fig10_theory.run,
        "fig11": fig11_stagewise.run,
        "hlo": strassen_hlo.run,
        "roofline": roofline_table.run,
        "serve_load": serve_load.run,
        "spin_scaling": spin_scaling.run,
    }
    wanted = sys.argv[1:] or list(suites)
    print("name,us_per_call,derived")
    for name in wanted:
        if name not in suites:
            raise SystemExit(f"unknown suite {name!r}; have {sorted(suites)}")
        suites[name]()


if __name__ == "__main__":
    main()
