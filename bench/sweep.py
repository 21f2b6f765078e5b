"""Find the highest rate an open-loop cell sustains, in one process.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 500,1000,2000

Builds the cell's service once, then offers each rate for ``--seconds``
with the cell's traffic mix (writer included) and prints one JSON line
per rate: achieved rate, p50/p99 from when each query was due, the
median latency of the window's first and last quarter (a backlog that
grows shows as the last reading far above the first), and the
generator's lag.  The rate a cell's traffic file offers is set once from
such a sweep; the benchmark's own runs never search for it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import types
from pathlib import Path

import numpy as np

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from bench import readers, run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    rates = [float(r) for r in args.rates.split(",")]
    cell = spec.load_cell(args.workload)
    if cell.traffic["loop"] != "open":
        sys.exit("sweep: only open-loop cells have a rate")
    devices = run.require_chips(cell.chips)
    run.setup_compile_cache()
    system = run.System(cell, args.seed, devices)
    # one writer, sized for every window, keeps its ids and live set
    # from one window to the next
    first = run.Load(cell, args.seed, args.seconds * len(rates), system)
    first.warm_up(args.seed, system.centers)
    run.settle()
    for i, rate in enumerate(rates):
        c = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                   rate_qps=rate))
        load = run.Load(c, args.seed + i + 1, args.seconds, system)
        load.writer = first.writer
        win = load.run(args.seconds)
        lat = readers.latencies_ms(types.SimpleNamespace(**vars(win)))
        q = len(lat) // 4
        order = np.argsort([r.due for r in win.requests
                            if r.result is not None])
        lat_by_due = lat[order]
        done = sum(1 for r in win.requests if r.result is not None
                   and win.t0 <= r.done <= win.t_close)
        lag = [r.submitted - (win.t0 + r.due) for r in win.requests]
        print(json.dumps({
            "rate_qps": rate, "offered": len(win.requests),
            "answered_in_window_qps": done / args.seconds,
            "failed": sum(1 for r in win.requests if r.result is None),
            "p50_ms": readers.p(lat, 50), "p99_ms": readers.p(lat, 99),
            "first_quarter_p50_ms": readers.p(lat_by_due[:q], 50),
            "last_quarter_p50_ms": readers.p(lat_by_due[-q:], 50),
            "lag_p99_ms": float(np.percentile(lag, 99)) * 1e3,
            "write_p99_ms": readers.p(
                [(w.done - (win.t0 + w.due)) * 1e3 for w in win.writes],
                99),
        }), flush=True)
        load.writer = None          # first's, released below
        load.release()
    first.release()
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
