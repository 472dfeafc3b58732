"""Benchmark entry point — one section per paper table/figure + system rows.

Prints ``name,us_per_call,derived`` CSV lines:

  membench_*    paper Tables 1-3 (simulated Tesla/Fermi + measured host)
  fig1/2/3_*    paper Figures 1-3 (primitive ops/s vs concurrency)
  table5_*      best-implementation auto-selection vs the paper's Table 5
  headline_*    paper Section-7 headline speedups
  host_*        real-thread host-row sweeps
  kernel_*      Pallas kernel checks (interpret tier)
  roofline*     the 40-cell dry-run roofline table (artifacts required)

Usage: PYTHONPATH=src python -m benchmarks.run [--fast] [--section NAME]
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller sweeps (CI mode)")
    ap.add_argument("--section", default=None,
                    choices=("membench", "primitives", "hostbench",
                             "kernels", "roofline"))
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    t_start = time.time()
    sections = []
    if args.section in (None, "membench"):
        from benchmarks import membench
        sections.append(("membench", membench.main))
    if args.section in (None, "primitives"):
        from benchmarks import primitives
        sections.append(("primitives", lambda: primitives.main(fast=args.fast)))
    if args.section in (None, "hostbench"):
        from benchmarks import hostbench
        sections.append(("hostbench", lambda: hostbench.main(
            threads=4 if args.fast else 8, ops=100 if args.fast else 300)))
    if args.section in (None, "kernels"):
        from benchmarks import kernelbench
        sections.append(("kernels", kernelbench.main))
    if args.section in (None, "roofline"):
        from benchmarks import roofline_report
        sections.append(("roofline", roofline_report.main))

    print("name,us_per_call,derived")
    for name, fn in sections:
        t0 = time.time()
        try:
            for row in fn():
                print(row)
        except Exception as e:  # pragma: no cover
            print(f"{name}_SECTION_FAILED,0.0,{e!r}", file=sys.stderr)
            raise
        print(f"# section {name} took {time.time() - t0:.1f}s",
              file=sys.stderr)
    print(f"# total {time.time() - t_start:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
