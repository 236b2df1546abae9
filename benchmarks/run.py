#!/usr/bin/env python3
"""The benchmark's entry: one process, one cell, once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names the cell's
configuration (``benchmarks/configs/<name>.json``), its traffic mix
(``benchmarks/traffic/<name>.json``) and the per-layer metrics, each of which
has a reader of its own (``benchmarks/metrics/<name>.py``).  The configuration
names its driver (``benchmarks/drivers/<driver>.py``) and its plain reference
(``benchmarks/reference/<module>.py``).  A later PR adds cells, traffic mixes
and metrics as new files and new entries; nothing here needs an edit.

``main`` always demands the accelerator the cell asks for and has no option
that waives it: a CPU number can never come out under a device metric's name.
The LAST stdout line is the result object."""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()  # set-up starts when the process does

import argparse
import importlib
import importlib.util
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve_cell(bench: dict, workload: str, root: Path = ROOT):
    """The cell, its configuration (file contents) and its traffic mix."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmark: no workload {workload!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = json.loads((root / entry["file"]).read_text())
    folder = Path(entry["file"]).parent.parent / "traffic"
    traffic = json.loads((root / folder / f"{cell['traffic']}.json").read_text())
    return cell, config, traffic


def metric_readers(bench: dict, cell: dict, root: Path = ROOT) -> list:
    """``(name, unit, reader)`` for every per-layer metric this cell reports;
    each reader is ``read(ctx) -> float | None`` in a file of its own."""
    out = []
    folder = root / bench["paths"][0] / "metrics"
    for m in bench["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        path = folder / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            f"benchmarks.metrics._{m['name'].replace('.', '_').replace('-', '_')}",
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        out.append((m["name"], m["unit"], mod.read))
    return out


def demand_devices(chips: int):
    """The cell's accelerator, or no run.  There is no fallback."""
    import jax

    from benchmarks.lib.peaks import chip_peaks

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, jax.devices()[0].platform "
                         f"is {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, jax "
                         f"finds {len(devices)}")
    chip_peaks(devices[0].device_kind)  # an unknown kind is an error here
    return devices[:chips]


def place_compile_cache(root: Path = ROOT) -> str:
    """A fixed directory inside the checkout (the path is part of every
    entry's key), unless the machine names one.  Every program is cached,
    however short its compile, so that only a checkout's first run compiles."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        placed = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    bench = load_benchmark()
    cell, config, traffic = resolve_cell(bench, args.workload)
    devices = demand_devices(int(cell["chips"]))
    place_compile_cache()

    from benchmarks.lib.monitor import CompileClock

    driver = importlib.import_module(f"benchmarks.drivers.{config['driver']}")
    result = driver.run(
        cell=cell, config=config, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace),
        devices=devices, t_process_start=T_PROCESS_START,
        clock=CompileClock(),
        metric_readers=metric_readers(bench, cell) if args.trace else None)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
