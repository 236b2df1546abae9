"""The program's own per-epoch phase records (``tdfo_tpu.obs.trace``
``epoch_history()``: what ``Trainer._train_epoch`` spent in each ``tdfo:``
phase, on the host's monotonic clock) reduced to per-layer metrics.

A record is ``{"epoch", "steps", "loop_s", "phases": {name: [seconds, count,
max_seconds]}, ...}``.  The window's epochs are those after the warm-up (epoch
0); a value is computed per epoch and the median over epochs is reported, so
the one epoch of a traced run that ran under the profiler does not set it.  A
program without the records (a parent commit from before they existed) reads
as nothing; a program with them but an empty window, or without a phase that
every epoch runs, is an error of the run, never a 0."""

from __future__ import annotations

import statistics


def window_epochs() -> list[dict] | None:
    try:
        from tdfo_tpu.obs.trace import epoch_history
    except ImportError:
        return None
    history = epoch_history()
    # a process that has driven several runs (the tests) keeps them all:
    # this run's window is what follows the last warm-up
    warm = max((i for i, r in enumerate(history) if r["epoch"] == 0), default=-1)
    records = [r for r in history[warm + 1:] if r["epoch"] >= 1 and r["steps"]]
    if not records:
        raise RuntimeError(
            "the program keeps epoch phase records but none of an epoch >= 1 "
            f"with steps after the warm-up ({len(history)} records in all)")
    return records


def phase(record: dict, name: str) -> list:
    """``[seconds, count, max_seconds]`` of a phase every epoch runs."""
    try:
        return record["phases"][name]
    except KeyError:
        raise RuntimeError(
            f"epoch {record['epoch']} has no phase {name!r}; it ran "
            f"{sorted(record['phases'])}") from None


def seconds(record: dict, name: str) -> float:
    return phase(record, name)[0]


def median_over_epochs(value) -> float | None:
    """``value(record)`` per window epoch, the median of them."""
    records = window_epochs()
    if records is None:
        return None
    return statistics.median(value(r) for r in records)


def ms_per_step(name: str) -> float | None:
    return median_over_epochs(lambda r: 1e3 * seconds(r, name) / r["steps"])
