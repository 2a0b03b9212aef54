#!/usr/bin/env python3
"""Sweep of offered rates for an open-loop cell, to find its knee.

    python3 bench/sweep_knee.py --workload deit-small.online-poisson \
        --rates 10,20,30,40 --seconds 8

In one process: set up and warm up the cell once, then for each offered
rate serve the mix's arrivals (at that rate instead of the mix's own) for
``--seconds`` and print one JSON line: requests offered, the completed rate
over the window, how many requests were outstanding at the middle and at
the end of the window (a queue that grows is past the knee), the p50
and p95 latency of the requests that completed, how many of them ran as
express lanes, and the programs lowered inside the window. The engine
drains between rates. The knee is the highest rate at which the completed
rate keeps up with the offered one and the queue does not grow; the mix
file's ``rate_rps`` is set to 0.8 of it by hand, and checked with runs of
the cell's full length (a short rung misses a queue that a late burst
starts). Needs the chip.
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


def sweep(cell, rates, seconds: float, seed: int, require_tpu=True,
          cfg=None, out=sys.stdout):
    from harness import check as C
    from harness import drive
    from harness import main as M
    from harness import traffic as T

    M.device_info(cell.chips, require_tpu)
    M.enable_cache()
    drive.CompileWatch.get()
    # set-up and warm-up once; the first rate's window is the run's own
    records, engine, info = drive.run(cell, seed, 0.5, False, T_START,
                                      cfg=cfg)
    pool = info["pool"]
    rows = []
    for rate in rates:
        mix = dict(cell.mix, rate_rps=rate)
        reqs = drive.open_requests(mix, seconds, seed, len(pool))
        win = drive.Window(engine, mix, seed, pool, None)
        backlog = {}
        t0 = time.perf_counter()

        def probe(now, _w=win, _t0=t0):
            if "mid" not in backlog and now >= _t0 + seconds / 2:
                backlog["mid"] = len(_w.requests) - len(_w.out)

        win.on_tick = probe
        watch = drive.CompileWatch.get()
        compiles0 = watch.programs
        watch.names = []
        win.open(t0, reqs, drain_s=0.0)
        t_close = time.perf_counter()
        backlog["end"] = len(win.requests) - len(win.out)
        done = win.out.done_t
        n_done = sum(1 for t in done.values() if t <= t_close)
        while len(win.requests) > len(win.out):
            win._tick()
        lowered, watch.names = watch.names, None
        n_segs = len(engine.segments.plan)
        lanes = sum(1 for r in win.requests.values()
                    if C.served_by(r, n_segs) == "lane")
        lat = [(done[r.uid] - (t0 + r.due_s)) * 1e3
               for r in reqs if r.uid in done]
        row = {"rate_rps": rate, "offered": len(reqs),
               "completed_rps": n_done / (t_close - t0),
               "backlog_mid": backlog.get("mid"),
               "backlog_end": backlog["end"],
               "p50_ms": T.percentile(lat, 50),
               "p95_ms": T.percentile(lat, 95),
               "drain_s": time.perf_counter() - t_close,
               "lanes": lanes,
               "compiles": watch.programs - compiles0,
               "lowered": lowered[:8]}
        print(json.dumps(row), file=out, flush=True)
        rows.append(row)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    from harness import main as M
    from harness import spec
    try:
        sweep(spec.cell(args.workload),
              [float(r) for r in args.rates.split(",")], args.seconds,
              args.seed)
    except M.NoAccelerator as e:
        print(f"sweep_knee.py: {e}; nothing was run", file=sys.stderr)
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main())
