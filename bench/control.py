"""Readings the correctness limits are set from: the program's and the
control's, on many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

Builds the cell's service once (its points from the first seed), then
for each seed drives one window of the cell's traffic made from that
seed at the cell's own load, and prints one JSON line with the numbers
``correct`` compares (``bench/reference.py``):

* ``program``: the program's answers, sampled as a run samples them,
  against the float64 reference;
* ``control``: the reference with its distances at ``high`` (three
  bfloat16 passes) put in the program's place, on the same queries at
  the same generations, against the same float64 reference.

The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from bench import reference, run, spec  # noqa: E402


def readings(system, load, win, seed: int, sample: int) -> dict:
    """Program and control numbers for one window."""
    run.log_writes(system, load)
    answers = run.sample_answers(load, win, seed, sample)
    want = run.truth(system, answers)
    program = reference.compare(system.live, answers, want)
    program["missing"] = (sum(1 for r in win.requests if r.result is None)
                          + sum(1 for b in win.writes if b.error is not None))
    low = run.truth(system, answers, reference.high3_distances)
    control = reference.compare(
        system.live, [a[:3] + got for a, got in zip(answers, low)], want)
    return {"checked": len(answers), "program": program, "control": control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = spec.load_cell(args.workload)
    devices = run.require_chips(cell.chips)
    run.setup_compile_cache()
    system = run.System(cell, seeds[0], devices)
    # one writer, sized for every window, keeps its ids and live set
    first = run.Load(cell, seeds[0], args.seconds * len(seeds), system)
    first.warm_up(seeds[0], system.centers)
    run.settle()
    sample = cell.config["check"]["sample"]
    for seed in seeds:
        load = run.Load(cell, seed, args.seconds, system)
        load.writer = first.writer
        win = load.run(args.seconds)
        print(json.dumps({"seed": seed, **readings(system, load, win, seed,
                                                   sample)}), flush=True)
        load.writer = None          # first's, released below
        load.release()
    first.release()
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
