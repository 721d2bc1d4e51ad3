"""Run one cell of the benchmark once:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up, a measured window of about ``--seconds`` on the cell's chips, and
the comparison with the plain reference that decides ``correct``.  The
last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``checks``: each number compared with its
limit).  Those numbers are also the last lines of standard error.  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.

It exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell needs, or when the repository's program is missing.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    import cell
    try:
        out = cell.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START)
    except cell.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, v in out["checks"].items():
        print(f"{k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
