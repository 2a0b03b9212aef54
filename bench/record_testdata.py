#!/usr/bin/env python3
"""Record the small chip trace that the trace-reduction tests read.

    python3 bench/record_testdata.py <out_dir>

Runs ``deit-small.offline`` for two seconds with a 0.15 s traced
sub-window, then writes ``<out_dir>/offline_trace.xplane.pb.gz`` (the
profiler's trace, gzipped) and ``<out_dir>/offline_trace.json`` (what the
reduction read from it when it was recorded). Copy both into
``bench/testdata``. Needs the chip.
"""
import time

T_START = time.perf_counter()

import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main() -> int:
    from harness import main as M
    from harness import xtrace as X

    out = sys.argv[1]
    os.makedirs(out, exist_ok=True)
    name = "deit-small.offline"
    result = M.run_cell(name, 7, 2.0, True, T_START,
                        mix={"trace_seconds": 0.15})
    src = X.find_xplane(str(M.CACHE / "trace" / name))
    dst = os.path.join(out, "offline_trace.xplane.pb.gz")
    with open(src, "rb") as f, gzip.open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    tr = X.load(dst)
    summary = {"window_s": tr.window_s, "busy_s": X.busy_s(tr),
               "idle_share": X.idle_share(tr),
               "sbmm": X.matching_seconds(tr, "sbmm"),
               "top_ops": X.top_ops(tr),
               "idle_by_host_span": X.idle_by_host_span(tr),
               "result": result}
    with open(os.path.join(out, "offline_trace.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({k: summary[k] for k in
                      ("window_s", "busy_s", "idle_share", "sbmm")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
