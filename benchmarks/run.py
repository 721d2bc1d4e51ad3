"""Benchmark entrypoint: one function per paper table/figure + the roofline.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).

  table1   -> benchmarks.bug_table          (silent-bug detection sweep)
  fig7+8   -> benchmarks.threshold_curves   (FP thresholds vs depth; bug sep)
  fig9     -> benchmarks.fp8_smoothness     (FP8 recipes stay smooth)
  sec6.4   -> benchmarks.overhead           (detection latency vs naive)
  kernels  -> benchmarks.kernel_bench       (Pallas vs oracle sweep)
  checker  -> benchmarks.checker_bench      (batched vs loop trace checking)
  roofline -> benchmarks.roofline           (3-term analysis; --roofline)

``--json PATH`` additionally writes the emitted rows as machine-readable
JSON (name -> us_per_call) so PRs leave a perf trajectory behind.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks.common import run_bench, write_json

BENCHES = (("kernels", "kernel_bench"), ("checker", "checker_bench"),
           ("supervisor", "supervisor_bench"), ("fp8", "fp8_smoothness"),
           ("curves", "threshold_curves"), ("bug_table", "bug_table"),
           ("overhead", "overhead"), ("roofline", "roofline"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: bug_table,curves,fp8,overhead,kernels,"
                         "checker,supervisor,roofline")
    ap.add_argument("--roofline", action="store_true",
                    help="include the (slow, 512-device) roofline sweep")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON {name: us_per_call}")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-step smoke mode: exercises every selected "
                         "bench end to end but writes NO BENCH_*.json "
                         "(keeps the tracked rows honest) — the test "
                         "suite's rot guard")
    args = ap.parse_args()
    if args.smoke:
        import os
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    want = set(args.only.split(",")) if args.only else None

    def on(name):
        return want is None or name in want

    print("name,us_per_call,derived")
    failures = []
    # each bench runs in a child process: the parent never touches JAX, so
    # on an accelerator the one process that uses the chip is the bench's
    for name, module in BENCHES:
        if not on(name):
            continue
        if name == "roofline" and not (args.roofline
                                       or (want and "roofline" in want)):
            continue
        try:
            run_bench(module)
        except Exception:
            traceback.print_exc()
            failures.append(name)

    if args.json:
        write_json(args.json)

    if failures:
        print(f"# {len(failures)} benchmark(s) failed: {failures}")
        sys.exit(1)
    print("# all benchmarks completed")


if __name__ == "__main__":
    main()
