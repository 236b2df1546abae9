"""Tests of the benchmark's own code.  They run on the CPU (the harness's
``main`` is the only place that demands a chip, and one test asserts that it
does); sizes are arguments of the harness's functions, so the code under test
is the code the chip runs."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)

from bench_examples import CELL, EXAMPLE, TINY, dlrm_example  # noqa: E402


@pytest.fixture(scope="session")
def bench():
    from benchmarks import run

    return run.load_benchmark()


@pytest.fixture(scope="session")
def resolve(bench):
    """``(cell, config, traffic)`` of the committed cell, or of the example a
    later PR could add, which shares the committed traffic mix."""
    from benchmarks import run

    def go(workload: str):
        cell, config, traffic = run.resolve_cell(bench, CELL)
        if workload == EXAMPLE:
            cell = {**cell, "name": EXAMPLE, "config": "dlrm-example"}
            config = dlrm_example()
        return cell, config, traffic

    return go


@pytest.fixture
def run_tiny(bench, resolve):
    """Drive one whole run of a cell at a tiny size on a CPU device: the
    harness's look for a chip is skipped, nothing else is."""
    from benchmarks import run
    from benchmarks.drivers import train_epoch
    from benchmarks.lib.monitor import CompileClock

    def go(workload: str, *, seed: int = 2**31 + 11, trace: bool = False,
           seconds: float = 0.0):
        cell, config, traffic = resolve(workload)
        return train_epoch.run(
            cell=cell, config=config, traffic=traffic, seed=seed,
            seconds=seconds, trace=trace, devices=jax.devices()[:1],
            t_process_start=run.T_PROCESS_START, clock=CompileClock(),
            sizes=TINY[workload],
            metric_readers=run.metric_readers(bench, cell) if trace else None)

    return go
