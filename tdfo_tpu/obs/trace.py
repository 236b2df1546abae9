"""Span-based causal tracing for the online loop (``[telemetry] trace``).

The PR-7 flight recorder observes components in isolation (counters see the
step, ``events.jsonl`` sees compiles, the frontend JSONL sees requests);
this module is the correlation layer that observes the loop as ONE system:
every component appends structured spans to a per-component
``trace-<component>.jsonl`` sink under one trace directory, carrying the
propagated ids that chain a served request ``(replica, seq)`` to the replay
batch that consumed it, the online cycle that trained on it, and the
version/digest the cycle produced (Monolith's end-to-end staleness
monitoring idiom; torchrec's ``train_pipeline`` stage timing).  The offline
assembler (``obs/aggregate.py``, ``launch.py obs``) joins the sinks into
per-cycle causal timelines.

Contracts (tests/test_trace.py):

  * **Off is free.**  ``trace = false`` (the default) leaves ``emit`` as an
    early return — no file I/O, no id minting, and the traced step jaxpr is
    byte-identical (spans are host-side only; nothing rides the step
    program).
  * **Every line is complete.**  Sinks are opened, appended one complete
    JSON line, and closed per record (the ``obs/events.py`` shape), then
    size-capped via ``utils/logrotate.maybe_rotate_path`` — a kill between
    appends never tears a line, so the assembler needs no torn-tail logic.
  * **Ids are deterministic.**  Span ids come from a locked module counter,
    never ``uuid``/``random``/``secrets`` — restarted runs stay
    reproducible, and the causal JOIN keys are the domain ids (replica,
    seq, cycle, version, digest) rather than the span id, so id reuse
    across restarts is harmless.  ``tests/test_quality.py`` confines both
    id minting and monotonic-clock differencing to this module.

Clock discipline: ``ts`` is ``time.time()`` (bare use, never differenced —
the only clock comparable across processes and sinks, what freshness lag
is computed from offline); durations are measured with the monotonic clock
via ``clock()``/``elapsed_ms()``/``elapsed_s()`` below, the single
sanctioned home for monotonic differencing so host-loop timing all flows
through one auditable site (the ``time.time()`` twin of this rule is
``tests/test_quality.py::test_no_wall_clock_differencing_around_device_work``).
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from pathlib import Path
from typing import Iterator

from tdfo_tpu.utils.logrotate import maybe_rotate_path

_LOCK = threading.Lock()
_ROOT: Path | None = None
_ROTATE_BYTES = 0
_NEXT_ID = 0


def configure(root_dir: str | Path | None = None, *,
              rotate_bytes: int = 0) -> None:
    """Attach the module-global trace sink directory (``None`` detaches).

    The module-global configure/active shape of ``obs/events.py`` and
    ``utils/faults.py``: emission sites call ``emit`` unconditionally and
    the deconfigured path falls through for free."""
    global _ROOT, _ROTATE_BYTES, _NEXT_ID
    with _LOCK:
        _ROOT = Path(root_dir) if root_dir is not None else None
        _ROTATE_BYTES = int(rotate_bytes)
        _NEXT_ID = 0
        if _ROOT is not None:
            _ROOT.mkdir(parents=True, exist_ok=True)


def active() -> bool:
    return _ROOT is not None


def trace_dir() -> Path | None:
    return _ROOT


def clock() -> float:
    """Monotonic timestamp for host-loop interval timing.

    Pair with ``elapsed_ms``/``elapsed_s`` — the subtraction happens HERE
    (the one sanctioned monotonic-differencing site) so callers never
    lexically difference a clock, and the quality gate can audit every
    wall-time measurement in one place.  For device work the interval
    must end in ``block_until_ready`` or a value fetch; ``bench.chain_time``
    is the inherited chain-differencing method (to be re-validated,
    ROADMAP S0)."""
    return time.monotonic()


def elapsed_ms(t0: float) -> float:
    """Milliseconds elapsed since ``t0`` (a ``clock()`` value)."""
    return (time.monotonic() - t0) * 1000.0


def elapsed_s(t0: float) -> float:
    """Seconds elapsed since ``t0`` (a ``clock()`` value)."""
    return time.monotonic() - t0


def emit(component: str, kind: str, **fields) -> None:
    """Append one complete span line to ``trace-<component>.jsonl``.

    No-op (early return, no I/O) unless ``configure`` attached a sink
    directory.  Values must be JSON-serializable — callers pass domain ids
    and plain numbers, never arrays."""
    root = _ROOT
    if root is None:
        return
    global _NEXT_ID
    with _LOCK:
        if _ROOT is None:  # detached while waiting on the lock
            return
        _NEXT_ID += 1
        rec = {"span": _NEXT_ID, "ts": time.time(), "component": component,
               "kind": kind, **fields}
        path = _ROOT / f"trace-{component}.jsonl"
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        if _ROTATE_BYTES:
            maybe_rotate_path(path, _ROTATE_BYTES)


@contextlib.contextmanager
def span(component: str, kind: str, **fields) -> Iterator[dict]:
    """Time a region and emit one span with ``dur_ms`` on exit.

    Yields a dict the body may add fields to (verdict, counts); the span is
    emitted even when the body raises, so killed stages still leave their
    partial timing behind.  When tracing is off the body runs untouched
    (the yielded dict just falls on the floor)."""
    if _ROOT is None:
        yield {}
        return
    extra: dict = {}
    t0 = clock()
    try:
        yield extra
    finally:
        emit(component, kind, dur_ms=round(elapsed_ms(t0), 3),
             **{**fields, **extra})
