#!/usr/bin/env python3
"""The control of a cell's answer check, run on the chip.

    python bench/control.py --workload ep-batch --seeds 11,12,13 --seconds 3

For each seed: the cell as the benchmark runs it, but served from a
device layout whose rows keep only their first 8 entries, one sublane
group of the (8, 128) tiling: the cut a shorter, better-aligned row
length would tempt a kernel to make. The configuration guarantees exact
answers; answers that need an entry past the cut go wrong, so the check
has to read ``correct: false``. Rows are ordered by hub rank, so a cut
at half the longest row costs only about 1 answer in 4,000 and would
not test the check at all. One JSON line per seed: the check's numbers,
and ``row_len`` of the true layout against the cut one.
"""
from __future__ import annotations

import time


import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


#: entries each row keeps in the control's layout
CUT = 8


def truncate_rows(run) -> None:
    """Serve ``run`` from a layout whose rows keep only their first
    :data:`CUT` entries."""
    from repro.core.device_index import DeviceIndex
    svc = run.svc
    svc.device_index = DeviceIndex.from_frozen(svc.frozen, svc.mr_ids,
                                               row_len=CUT)
    svc.executor.device_index = svc.device_index
    run.row_len = CUT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.lib import cell as cl
    bench = cl.load_json(ROOT / "BENCHMARK.json")
    cell = cl.Cell.from_benchmark(bench, args.workload, False)
    for seed in (int(x) for x in args.seeds.split(",")):
        full = {}

        def hook(run):
            full["row_len"] = run.row_len
            truncate_rows(run)
            full["cut"] = run.row_len
        out = cl.execute(cell, seed, args.seconds, False,
                         time.perf_counter(), hook=hook)
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              control=f"rows cut to {CUT} entries",
                              correct=out["correct"], **full,
                              check=out["check"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
