"""Device time by the program's named scopes, from a kept trace.

``tf_op`` (the HLO ``op_name``, where a ``jax.named_scope`` lands) sits on
event METADATA that ``jax.profiler.ProfileData`` hides, so this goes through
the wire-format reader of ``benchmarks/tools/scope_dump.py``.  An operation
counts under the INNERMOST of the asked-for scopes in its ``tf_op`` (the last
one named: a backward operation reads ``transpose(jvp(<scope>))``, a
rematerialised one ``checkpoint/<scope>``), by its exclusive time, so a
``while`` and its body are not counted twice.  Per executed step program of
the first device plane.  A trace with no device plane, or a program without
the scopes, gives nothing: the caller leaves the metric out."""

from __future__ import annotations

import re
from pathlib import Path

from benchmarks.tools import scope_dump


def scope_ms(trace_dir: str | Path, names: tuple[str, ...]
             ) -> dict[str, float] | None:
    """``{scope: device milliseconds a step}`` for the scopes found."""
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return None
    rx = re.compile(r"\b(" + "|".join(map(re.escape, names)) + r")\b")
    for name, meta, lines in scope_dump.planes(files[-1]):
        if not name.startswith("/device:") or "XLA Ops" not in lines:
            continue
        runs: dict[str, list] = {}      # program -> [device time, executions]
        for mid, _, dur in lines.get("XLA Modules", ()):
            rec = runs.setdefault(meta.get(mid, ("?", {}))[0], [0, 0])
            rec[0] += dur
            rec[1] += 1
        if not runs:
            return None
        steps = max(runs.values())[1]   # the step program took most time
        out: dict[str, float] = {}
        for mid, ps in scope_dump.self_times(lines["XLA Ops"]):
            found = rx.findall(meta.get(mid, ("?", {}))[1].get("tf_op") or "")
            if found:
                out[found[-1]] = out.get(found[-1], 0.0) + ps
        return {k: v / 1e9 / steps for k, v in out.items()}
    return None
