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

Train-loop phases (``phase``/``epoch_phases``/``epoch_history``, the second
half of this module) are a different shape for a different rate: a JSONL
append per record is right for an online cycle and wrong for a 6 ms step, so
a phase is a ``jax.profiler.TraceAnnotation`` (``tdfo:<name>``: free while no
profiler session runs, on the device trace's clock while one does) plus an
in-memory per-epoch accumulator that is always on and touches no file.
"""

from __future__ import annotations

import collections
import contextlib
import json
import threading
import time
from pathlib import Path
from typing import Iterator

from jax.profiler import TraceAnnotation

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
    must end in ``block_until_ready`` or a value fetch.  Speed is measured
    on the benchmark's clock: ``benchmarks/lib/monitor.py::now`` around
    whole ``Trainer.train_epoch`` calls that end in a value fetch, device
    times from the profiler trace (``PERF.md`` section 2)."""
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


# ------------------------------------------------------- train-loop phases

HISTORY_EPOCHS = 64
_HISTORY: collections.deque = collections.deque(maxlen=HISTORY_EPOCHS)
_now = time.monotonic  # the phases' clock (tests substitute a fake)
_session_on = TraceAnnotation.is_enabled  # the profiler's own atomic load


class _Thread(threading.local):
    epoch = None  # this thread's chain: the epoch it opened, or its share of
    #               one it joined (a class default: a missing attribute costs
    #               a raised AttributeError a read)


_THREAD = _Thread()


def phase(name: str):
    """``with phase("dispatch"): ...`` — one named stretch of the train loop.

    A ``jax.profiler.TraceAnnotation("tdfo:<name>")`` wherever it runs: an
    atomic load while no profiler session runs, a host span on the device
    trace's clock while one does.  If an ``epoch_phases`` accumulator is
    open ON THIS THREAD, or this thread has joined one (``join_epoch``),
    the duration is also added to it under ``name`` (seconds, count,
    longest single occurrence, and self time: the part no nested phase
    covers); anywhere else (eval, serving, a thread that has not joined, a
    benchmark's probe of the stream) it only annotates.  Class-based, no
    generator: it runs several times a step.  A phase closes on the thread
    and inside the ``next()`` it opened in (never hold one across a
    ``yield``), and does not nest inside a phase of its own name."""
    acc = _THREAD.epoch
    if acc is None:
        return TraceAnnotation("tdfo:" + name)
    ph = acc._phases.get(name)
    if ph is None:
        with _LOCK:  # close() reads a joined thread's phases under it
            ph = acc._phases[name] = _Phase(name, acc)
    return ph


def tally(name: str, value: float = 1.0) -> None:
    """Add ``value`` to the count ``name`` of the epoch this thread opened
    or joined (``[sum, occurrences]`` under ``"tallies"`` of its record);
    nothing anywhere else.  For what a phase's seconds cannot say: how deep
    a queue stood at a take, how often it stood empty."""
    acc = _THREAD.epoch
    if acc is None:
        return
    t = acc._tallies.get(name)
    if t is None:
        with _LOCK:
            t = acc._tallies[name] = [0.0, 0]
    t[0] += value
    t[1] += 1


class _Phase:
    """One name's span and sums inside one epoch accumulator."""

    __slots__ = ("_label", "_ann", "_acc", "_outer", "_inner_s", "_t0",
                 "seconds", "count", "max_seconds", "self_seconds")

    def __init__(self, name: str, acc: "_Chain"):
        self._label = "tdfo:" + name
        self._ann = None
        self._acc = acc
        self.seconds = self.max_seconds = self.self_seconds = 0.0
        self.count = 0

    def __enter__(self):
        if _session_on():  # an annotation object serves one span only
            self._ann = TraceAnnotation(self._label)
            self._ann.__enter__()
        acc = self._acc
        self._outer, acc._open = acc._open, self
        self._inner_s = 0.0
        self._t0 = _now()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = _now() - self._t0
        self._acc._open = outer = self._outer
        if outer is None:
            self._acc._top_s += dt
        else:
            outer._inner_s += dt
        self.seconds += dt
        self.count += 1
        if dt > self.max_seconds:
            self.max_seconds = dt
        self.self_seconds += dt - self._inner_s
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        return False


class _Chain:
    """One thread's phases inside one epoch: the ``_Phase`` of each name,
    the innermost one open, the seconds of the outermost ones, the tallies.
    An epoch has one of its own (the thread that opened it) and one for
    every thread that joined it."""

    def __init__(self):
        self._phases: dict[str, _Phase] = {}
        self._open: _Phase | None = None
        self._top_s = 0.0
        self._tallies: dict[str, list] = {}


class epoch_phases(_Chain):
    """The accumulator round ONE ``_train_epoch`` call: ``with
    epoch_phases(epoch) as ep: ...; rec = ep.close(steps)``.

    ``close`` stops the epoch's clock and appends the record ::

        {"epoch", "steps", "loop_s", "loop_self_s",
         "phases": {name: [seconds, count, max_seconds]},
         "self_s": {name: seconds}, "tallies": {name: [sum, occurrences]}}

    to the bounded in-memory history (``epoch_history()``).  ``loop_s`` is
    the whole call on the phases' clock; ``loop_self_s`` is ``loop_s`` minus
    the outermost phases OF THE THREAD THAT OPENED THE EPOCH: its Python
    loop's own time.  The phases of threads that joined (``join_epoch``)
    are in ``phases`` and ``self_s`` under their names (seconds, counts and
    self time added, the longest call the longest of any thread) and
    nowhere else: they run beside the loop, not inside it.  Leaving the
    ``with`` without ``close`` (an epoch that raised) drops the accumulator
    and records nothing."""

    def __init__(self, epoch: int):
        super().__init__()
        self.epoch = int(epoch)
        self._joined: list[_Chain] = []

    def __enter__(self):
        if _THREAD.epoch is not None:
            raise RuntimeError("epoch_phases: an epoch is already open on "
                               "this thread")
        _THREAD.epoch = self
        self._t0 = _now()
        return self

    def close(self, steps: int) -> dict:
        loop_s = _now() - self._t0
        _THREAD.epoch = None
        phases: dict[str, list] = {}
        self_s: dict[str, float] = {}
        tallies: dict[str, list] = {}
        with _LOCK:
            for chain in (self, *self._joined):
                for k, p in chain._phases.items():
                    got = phases.setdefault(k, [0.0, 0, 0.0])
                    got[0] += p.seconds
                    got[1] += p.count
                    got[2] = max(got[2], p.max_seconds)
                    self_s[k] = self_s.get(k, 0.0) + p.self_seconds
                for k, (total, n) in chain._tallies.items():
                    got = tallies.setdefault(k, [0.0, 0])
                    got[0] += total
                    got[1] += n
            record = {
                "epoch": self.epoch, "steps": int(steps), "loop_s": loop_s,
                "loop_self_s": loop_s - self._top_s,
                "phases": phases, "self_s": self_s, "tallies": tallies,
            }
            _HISTORY.append(record)
        return record

    def __exit__(self, exc_type, exc, tb):
        if _THREAD.epoch is self:
            _THREAD.epoch = None
        return False


def current_epoch() -> "epoch_phases | None":
    """The epoch open on this thread, to hand to a thread that will work
    for it (``join_epoch``); ``None`` where none is open."""
    acc = _THREAD.epoch
    return acc if isinstance(acc, epoch_phases) else None


class join_epoch:
    """``with join_epoch(ep): ...`` on a thread that works FOR the epoch
    ``ep`` of another thread (``ep = current_epoch()`` taken there): this
    thread's phases add to ``ep``'s record under their names through an
    open-chain of this thread's own, so two threads never share a nesting;
    they stay out of ``loop_self_s`` and out of any phase of the opening
    thread.  ``ep`` ``None`` joins nothing: the phases only annotate.  What
    the thread adds after ``ep`` closed is dropped."""

    def __init__(self, epoch: "epoch_phases | None"):
        self._epoch = epoch

    def __enter__(self):
        if self._epoch is None:
            return self
        if _THREAD.epoch is not None:
            raise RuntimeError("join_epoch: an epoch is already open on "
                               "this thread")
        chain = _Chain()
        with _LOCK:
            self._epoch._joined.append(chain)
        _THREAD.epoch = chain
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._epoch is not None:
            _THREAD.epoch = None
        return False


def epoch_history() -> list[dict]:
    """The records of the last ``HISTORY_EPOCHS`` closed epochs of this
    process, oldest first.  ``configure`` leaves them alone."""
    with _LOCK:
        return list(_HISTORY)


def reset_epoch_history() -> None:
    with _LOCK:
        _HISTORY.clear()
