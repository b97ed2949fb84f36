"""Run every benchmark; print ``name,us_per_call,derived`` CSV.

One module per paper table/figure:
  bench_mm       — Table IV + Figs 9-11 (MM wall time + comm model)
  bench_strassen — Theorem 13 / CAPS comparison (Sect. III-F)
  bench_lcs      — Fig 12a (LCS PACO vs PO vs PA)
  bench_sort     — Fig 12b (sample sort)
  bench_dp       — Theorems 6/7 (1D, GAP)
  bench_moe      — framework integration: PACO dispatch in MoE
  bench_elastic  — arbitrary-p elasticity + HETERO straggler model
"""
from __future__ import annotations

from benchmarks import (bench_dp, bench_elastic, bench_lcs, bench_mm,
                        bench_moe, bench_sort, bench_strassen)
from benchmarks.common import flush_header


def main() -> None:
    flush_header()
    for mod in (bench_mm, bench_strassen, bench_lcs, bench_sort, bench_dp,
                bench_moe, bench_elastic):
        mod.main()


if __name__ == "__main__":
    main()
