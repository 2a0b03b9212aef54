#!/usr/bin/env python3
"""Benchmark of pruned-ViT serving on the chip: one run of one cell.

    python3 bench/run.py --workload <config>.<mix> --seed <n> \
        --seconds <s> --trace <0|1>

Builds the cell's configuration (``bench/configs``) with weights from
``--seed``, serves the cell's traffic mix (``bench/traffic``) through the
program's ``VisionEngine`` for ``--seconds``, and prints one JSON object as
the last line of standard output. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces a steady sub-window with the JAX
profiler and reports its per-layer metrics (``bench/metrics``). Either way
a sample of the served requests is compared with the plain reference, and
the numbers compared are printed beside their limits.

Exits 3, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from harness import main as M
    try:
        result = M.run_cell(args.workload, args.seed, args.seconds,
                            bool(args.trace), T_START)
    except M.NoAccelerator as e:
        print(f"run.py: {e}; nothing was run", file=sys.stderr)
        return 3
    for k, v in result["check"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
