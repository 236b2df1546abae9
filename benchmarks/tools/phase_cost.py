#!/usr/bin/env python3
"""What the program's ``phase`` spans cost the host loop with no profiler
session: one step's four phases (``next_batch`` > ``loader_next``,
``h2d_put``; ``dispatch``) round empty bodies, inside an open epoch
accumulator and outside one, against the same nesting of an empty context
manager.  By hand only; touches no device.

    python3 benchmarks/tools/phase_cost.py [--steps 300000]
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))


class _Empty:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def us_per_step(make, steps: int) -> float:
    t0 = time.perf_counter()
    for _ in range(steps):
        with make("next_batch"):
            with make("loader_next"):
                pass
            with make("h2d_put"):
                pass
        with make("dispatch"):
            pass
    return 1e6 * (time.perf_counter() - t0) / steps


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--steps", type=int, default=300_000)
    args = p.parse_args()

    from tdfo_tpu.obs import trace

    empty = _Empty()
    base = min(us_per_step(lambda name: empty, args.steps) for _ in range(3))
    outside = min(us_per_step(trace.phase, args.steps) for _ in range(3))
    with trace.epoch_phases(0) as epoch:
        inside = min(us_per_step(trace.phase, args.steps) for _ in range(3))
        epoch.close(args.steps)
    trace.reset_epoch_history()
    print(f"us a step of four phases, best of 3 x {args.steps} steps: "
          f"empty context managers {base:.2f}; phase outside an epoch "
          f"{outside:.2f} (+{outside - base:.2f}); inside an epoch "
          f"{inside:.2f} (+{inside - base:.2f})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
