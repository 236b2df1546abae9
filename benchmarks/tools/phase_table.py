#!/usr/bin/env python3
"""The program's whole phase record of a cell's window, by hand: runs the
cell (no profiler unless ``--trace 1``) and prints, per phase, milliseconds a
step, calls a step, the longest single call, self time and share of the loop
— the median over the window's epochs, as the per-layer readers take it — with
the loop's own time and what the spans leave of the harness's clock.

    python3 benchmarks/tools/phase_table.py --workload <name> [--seed n] [--seconds s] [--trace 0|1]
"""

from __future__ import annotations

import argparse
import importlib
import json
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def table(records: list[dict]) -> list[str]:
    med = statistics.median
    steps = med(r["steps"] for r in records)
    loop_ms = med(1e3 * r["loop_s"] / r["steps"] for r in records)
    lines = [f"{len(records)} epochs of {steps:g} steps; loop {loop_ms:.3f} ms "
             f"a step, of which the loop's own "
             f"{med(1e3 * r['loop_self_s'] / r['steps'] for r in records):.3f}",
             f"  {'phase':<16}{'ms/step':>9}{'self':>9}{'% loop':>8}"
             f"{'calls/step':>11}{'max ms':>9}"]
    names = sorted({n for r in records for n in r["phases"]},
                   key=lambda n: -med(r["phases"].get(n, [0])[0] for r in records))
    for n in names:
        per = lambda f: med(f(r) for r in records)
        got = lambda r: r["phases"].get(n, [0.0, 0, 0.0])
        lines.append(
            f"  {n:<16}{per(lambda r: 1e3 * got(r)[0] / r['steps']):9.3f}"
            f"{per(lambda r: 1e3 * r['self_s'].get(n, 0.0) / r['steps']):9.3f}"
            f"{per(lambda r: 100 * got(r)[0] / r['loop_s']):8.2f}"
            f"{per(lambda r: got(r)[1] / r['steps']):11.3f}"
            f"{per(lambda r: 1e3 * got(r)[2]):9.2f}")
    return lines


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args()

    from benchmarks import run as R
    from benchmarks.lib import phases
    from benchmarks.lib.monitor import CompileClock

    bench = R.load_benchmark()
    cell, config, traffic = R.resolve_cell(bench, args.workload)
    devices = R.demand_devices(int(cell["chips"]))
    R.place_compile_cache()
    driver = importlib.import_module(f"benchmarks.drivers.{config['driver']}")
    result = driver.run(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), devices=devices,
        t_process_start=R.T_PROCESS_START, clock=CompileClock(),
        metric_readers=R.metric_readers(bench, cell) if args.trace else None)
    records = phases.window_epochs()
    if records is None:
        raise SystemExit("phase_table: this program keeps no phase records")
    print("\n".join(table(records)))
    print(json.dumps({"epochs": records}))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
