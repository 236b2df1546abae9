#!/usr/bin/env python3
"""Look at one trace by hand before trusting code written against it: runs a
traced cell, keeps its ``.xplane.pb`` under ``chiprun_out/`` and prints which
planes and lines exist and what the longest events are called.

    python3 benchmarks/tools/trace_dump.py --workload <name> [--seed n] [--seconds s]
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--seconds", type=float, default=8.0)
    args = p.parse_args()

    from benchmarks import run as R
    from benchmarks.lib import trace as T
    from benchmarks.lib.monitor import CompileClock
    import importlib

    bench = R.load_benchmark()
    cell, config, traffic = R.resolve_cell(bench, args.workload)
    devices = R.demand_devices(int(cell["chips"]))
    R.place_compile_cache()
    keep = ROOT / "chiprun_out" / f"trace_{args.workload}"
    driver = importlib.import_module(f"benchmarks.drivers.{config['driver']}")
    result = driver.run(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=True, devices=devices,
        t_process_start=R.T_PROCESS_START, clock=CompileClock(),
        metric_readers=R.metric_readers(bench, cell), keep_trace=keep)
    planes = T.load(keep)
    for pl in planes:
        print(f"plane {pl.name!r}")
        for name, events in pl.lines.items():
            total = sum(e.dur_ns for e in events) / 1e6
            print(f"  line {name!r}: {len(events)} events, {total:.1f} ms")
            by = {}
            for e in events:
                k = by.setdefault(e.name, [0, 0, e.meta])
                k[0] += e.dur_ns
                k[1] += 1
            for nm, (ns, n, meta) in sorted(by.items(), key=lambda kv: -kv[1][0])[:25]:
                print(f"    {ns / 1e6:9.3f} ms x{n:<5} {nm[:90]}  | {meta[:300]}")
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
