#!/usr/bin/env python3
"""The readings a cell's limits are set from (PERF.md section 2), at the
cell's own size, on the chip.

    python3 benchmarks/tools/readings.py --workload <name> --seeds 1,2,3 \\
        [--program] [--control] [--faults]

``--control``  the plain reference computed in bfloat16, put in the program's
               place, against the float32 reference: the UPPER reading.
``--faults``   the reference with half of each batch left out (the mean taken
               over the rest), against the sound reference.
``--program``  the program itself through the driver (set-up, the recorded
               first steps, one epoch of window): the LOWER reading.  One
               process makes every seed, so programs come from jit's cache.
Control and faults need no ``Trainer``: their batches are the traffic
generator's first rows, their weights the benchmark's own."""

from __future__ import annotations

import argparse
import importlib
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--program", action="store_true")
    p.add_argument("--control", action="store_true")
    p.add_argument("--faults", action="store_true")
    args = p.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]

    import numpy as np

    from benchmarks import run as R
    from benchmarks.drivers import train_epoch as D
    from benchmarks.lib import compare as cmp, traffic as traffic_lib
    from benchmarks.lib.monitor import CompileClock

    bench = R.load_benchmark()
    cell, config, traffic = R.resolve_cell(bench, args.workload)
    devices = R.demand_devices(int(cell["chips"]))
    R.place_compile_cache()
    clock = CompileClock()
    columns = config["columns"]
    batch = int(config["program"]["per_device_train_batch_size"])
    dim = int(config["program"]["embed_dim"])
    out = []

    variants = ([("control_bfloat16", dict(compute="bfloat16"))] if args.control else []) \
        + ([("fault_half_batch", dict(fault="half_batch")),
            ("fault_frozen", dict(fault="frozen"))] if args.faults else [])
    if variants:

        shapes = dense_shapes(config)
        for seed in seeds:
            rows = traffic_lib.draw_rows(seed, D.RECORDED_STEPS * batch,
                                         columns=columns, traffic=traffic)
            feed = [{k: v[i * batch:(i + 1) * batch] for k, v in rows.items()}
                    for i in range(D.RECORDED_STEPS)]
            tinfo = D.table_info(columns["categorical"], dim, seed)
            sound, *_ = D.reference_side(config, tinfo, columns["continuous"],
                                         feed, seed, shapes)
            for name, kw in variants:
                got, *_ = D.reference_side(config, tinfo, columns["continuous"],
                                           feed, seed, shapes, **kw)
                _, compared = cmp.compare(got, sound, config["limits"])
                rec = {"what": name, "seed": seed,
                       **{k: v["value"] for k, v in compared.items()},
                       "leaf_gaps": {k: cmp.leaf_gaps(got[k], sound[k])
                                     for k in ("grad_norm", "update_norm")}}
                out.append(rec)
                print(json.dumps({k: v for k, v in rec.items()
                                  if k != "leaf_gaps"}), flush=True)
    if args.program:
        driver = importlib.import_module(f"benchmarks.drivers.{config['driver']}")
        for seed in seeds:
            res = driver.run(cell=cell, config=config, traffic=traffic,
                             seed=seed, seconds=0.0, trace=False,
                             devices=devices, t_process_start=R.T_PROCESS_START,
                             clock=clock)
            rec = {"what": "program", "seed": seed, "correct": res["correct"],
                   **res["observed"],
                   **{k: v["value"] for k, v in res["compared"].items()},
                   "leaf_gaps": res["leaf_gaps"]}
            out.append(rec)
            print(json.dumps({k: v for k, v in rec.items() if k != "leaf_gaps"}),
                  flush=True)
    dest = ROOT / "chiprun_out" / f"readings_{args.workload}.jsonl"
    dest.parent.mkdir(exist_ok=True)
    with dest.open("a") as f:
        for rec in out:
            f.write(json.dumps(rec) + "\n")
    return 0


def dense_shapes(config: dict) -> dict:
    """Shapes of the dense leaves, as the configuration file states them."""
    return {k: tuple(v) for k, v in config["reference"]["dense_shapes"].items()}


if __name__ == "__main__":
    sys.exit(main())
