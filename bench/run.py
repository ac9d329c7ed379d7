#!/usr/bin/env python3
"""Run one benchmark cell once, on the machine this starts on.

    python bench/run.py --workload ep-batch --seed 7 --seconds 10 --trace 0

Builds the cell's graph and index from ``--seed``, warms the shapes its
traffic uses, measures for ``--seconds``, and checks the answers of the
window against the plain reference. ``--trace 1`` profiles the window
and reports the cell's per-layer metrics instead of its end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
also ``breakdown``, and last ``check``, each compared number beside its
limit (also the last lines of standard error). Exits 3, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for,
and 1 on any other failure.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise FileNotFoundError(
                f"{ROOT / 'src'} holds no repro package: run from a "
                "checkout of the repository")
        sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
        from bench.lib import cell as cl
        bench = cl.load_json(ROOT / "BENCHMARK.json")
        cell = cl.Cell.from_benchmark(bench, args.workload, bool(args.trace))
        with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
            out = cl.execute(cell, args.seed, args.seconds, bool(args.trace),
                             T_START, log_dir=tmp)
    except Exception as e:  # noqa: BLE001 - every failure exits non-zero
        traceback.print_exc()
        return 3 if type(e).__name__ == "NoChip" else 1
    cl.report_check(out)
    print(json.dumps(result_line(out)), flush=True)
    return 0


def result_line(out: dict) -> dict:
    """The last line's object, its keys in order, ``check`` last."""
    line = {k: out[k] for k in ("correct", "attempted", "failed",
                                "metrics", "device")}
    if "breakdown" in out:
        line["breakdown"] = out["breakdown"]
    line["check"] = out["check"]
    return line


if __name__ == "__main__":
    sys.exit(main())
