"""The control: the plain reference with one stated guarantee broken,
put in the program's place, must come out not correct.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--size N]

For each seed it runs the cell's reference twice at the cell's own size
-- once as stated, once with ``control=True``, which breaks the ordering
guarantee the reference module describes -- and compares the second
with the first exactly as a run compares the program.  ``--size``
is the number of super-steps (closed cells) or events (streamed cells)
a measured window reaches.  It prints each number beside its limit and
exits 0 only when every seed's control fails a limit.  Host code only:
the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

from harness import load_cell, load_module  # noqa: E402


def readings(cell, seed: int, size: int) -> list:
    """``(name, reading, limit)`` of the control against the reference."""
    cfg, traffic = cell.cfg, cell.traffic
    ref = load_module("reference", cfg["model"])
    if traffic["drive"] == "closed":
        def sim(control):
            return ref.simulate(cfg, seed, size, control=control)
        seeded = cfg["num_lps"] * cfg["start_events"]
    else:
        from arrivals import make_source

        arr = traffic["arrivals"]
        rows = make_source(arr, seed).all_rows()

        def sim(control):
            return ref.simulate(cfg, rows, arr["n"], arr["block_size"],
                                size, control=control)
        seeded = 1
    want, got = sim(False), sim(True)
    got = dict(got, seeded=seeded, spilled=0, shed=0,
               batches=got.get("batches", 0))
    return ref.compare(got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--size", type=int, required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    all_fail = True
    for seed in args.seeds:
        out = readings(cell, seed, args.size)
        over = [n for (n, v, lim) in out if not v <= lim]
        all_fail &= bool(over)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "size": args.size, "over_limit": over,
                          "readings": {n: v for (n, v, _) in out}},
                         default=lambda x: np.asarray(x).tolist()),
              flush=True)
    return 0 if all_fail else 1


if __name__ == "__main__":
    sys.exit(main())
