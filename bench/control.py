#!/usr/bin/env python3
"""The control of a cell's answer check, run on the chip.

    python bench/control.py --workload ep-batch --seeds 11,12,13 --seconds 3

For each seed: the cell as the benchmark runs it, with the fault the
check has to catch put under the timed path: the stale control where the
cell's traffic writes, the rows control where it does not. One JSON line
per seed: the check's numbers, and what the control changed.

Rows (query cells): served from a device layout whose rows keep only
their first 8 entries, one sublane group of the (8, 128) tiling: the cut
a shorter, better-aligned row length would tempt a kernel to make. The
configuration guarantees exact answers; answers that need an entry past
the cut go wrong, so the check has to read ``correct: false``. Rows are
ordered by hub rank, so a cut at half the longest row costs only about
1 answer in 4,000 and would not test the check at all. A write rebuilds
the layout, so this control does not reach a cell that writes.

Stale (cells whose traffic writes): every write moves the graph and the
index, but the executor keeps serving the device layout and the cached
answers from before it: the shortcut of a write path that skips the
relayout or the eviction. The guarantee is read-your-writes, so the
answers that a write changes go wrong after it, and the check has to
read ``correct: false``.
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


def stale_writes(run) -> None:
    """Keep the executor on its pre-write layout and cache across every
    write of ``run``; the graph and the index still move."""
    svc = run.svc
    inner = svc.apply_delta

    def apply_delta(delta):
        ex, cache = svc.executor, svc.cache
        kept = ex.index, ex.frozen, ex.device_index
        cache.clear = lambda: None
        cache.invalidate_rows = lambda **kw: 0
        try:
            return inner(delta)
        finally:
            del cache.clear, cache.invalidate_rows
            ex.index, ex.frozen, ex.device_index = kept
    svc.apply_delta = apply_delta


def control(traffic: dict):
    """The fault a cell's control puts in, and how it is described: the
    stale control where the traffic writes, the rows control where it
    does not."""
    if "writes_per_s" in traffic:
        return stale_writes, ("writes served from the layout and cache "
                              "of before")
    return truncate_rows, f"rows cut to {CUT} entries"


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
    fault, what = control(cell.traffic)
    for seed in (int(x) for x in args.seeds.split(",")):
        full = {}

        def hook(run):
            full["row_len"] = run.row_len
            fault(run)
            full["served_row_len"] = run.row_len
        out = cl.execute(cell, seed, args.seconds, False,
                         time.perf_counter(), hook=hook)
        print(json.dumps(dict(workload=args.workload, seed=seed,
                              control=what,
                              correct=out["correct"], **full,
                              check=out["check"])), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
