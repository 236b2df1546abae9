#!/usr/bin/env python3
"""Device time of a kept trace by the program's named scopes.  By hand only.

    python3 benchmarks/tools/trace_dump.py --workload <name>    # keeps the trace
    python3 benchmarks/tools/scope_dump.py chiprun_out/trace_<name>

An ``XLA Ops`` event's own stats are offsets and durations; ``tf_op`` (the HLO
``op_name``, where a ``jax.named_scope`` lands), ``hlo_category`` and the
rest sit on the event METADATA, which ``jax.profiler.ProfileData`` does not
expose.  So this reads the ``.xplane.pb`` itself: a protobuf wire-format
reader of the few ``XPlane`` fields it needs (tensorflow/tsl
``profiler/protobuf/xplane.proto``), no dependency.

Prints device milliseconds per executed step program by the first of the
program's scopes in ``tf_op`` (``tdfo_tpu/train/sparse_step.py``), the share
with no ``tf_op`` at all (operations XLA inserted), and the custom calls with
their kernel names."""

from __future__ import annotations

import argparse
import re
import sys
from collections import defaultdict
from pathlib import Path

SCOPES = ("emb_lookup", "dense_fwd_bwd", "dense_update", "emb_update",
          "hot_update")
SCOPE = re.compile(r"\b(" + "|".join(SCOPES) + r")\b")


# ------------------------------------------------------- protobuf wire format


def _varint(buf: bytes, at: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[at]
        at += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, at
        shift += 7


def fields(buf: bytes):
    """``(field number, wire type, value)`` of one message: varints as ints,
    length-delimited fields as bytes, fixed 64/32 as bytes."""
    at, end = 0, len(buf)
    while at < end:
        key, at = _varint(buf, at)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, at = _varint(buf, at)
        elif wire == 2:
            n, at = _varint(buf, at)
            value, at = buf[at:at + n], at + n
        elif wire == 1:
            value, at = buf[at:at + 8], at + 8
        elif wire == 5:
            value, at = buf[at:at + 4], at + 4
        else:
            raise ValueError(f"wire type {wire} at byte {at}")
        yield number, wire, value


def _map_entry(buf: bytes) -> tuple[int, bytes]:
    key, value = 0, b""
    for n, _, v in fields(buf):
        if n == 1:
            key = v
        elif n == 2:
            value = v
    return key, value


def _stat(buf: bytes, stat_names: dict[int, str]) -> tuple[str, object]:
    """XStat: metadata_id 1; str_value 5, ref_value 7 (a stat metadata's
    name), the numeric kinds 2-4."""
    name, value = "", None
    for n, wire, v in fields(buf):
        if n == 1:
            name = stat_names.get(v, str(v))
        elif n == 5:
            value = v.decode("utf-8", "replace")
        elif n == 7:
            value = stat_names.get(v, "")
        elif n in (3, 4) and wire == 0:
            value = v
    return name, value


def planes(path: Path):
    """Per plane: name, ``{metadata id: (event name, {stat: value})}`` and
    ``{line name: [(metadata id, start_ps, duration_ps)]}``."""
    for n, _, plane in fields(path.read_bytes()):
        if n != 1:  # XSpace.planes
            continue
        name, lines_raw, meta_raw, stat_names = "", [], [], {}
        for pn, _, v in fields(plane):
            if pn == 2:
                name = v.decode()
            elif pn == 3:
                lines_raw.append(v)
            elif pn == 4:
                meta_raw.append(v)
            elif pn == 5:
                sid, body = _map_entry(v)
                stat_names[sid] = next(
                    (x.decode() for k, _, x in fields(body) if k == 2), "")
        meta = {}
        for entry in meta_raw:
            mid, body = _map_entry(entry)
            ev_name, stats = "", {}
            for k, _, x in fields(body):
                if k == 2:
                    ev_name = x.decode("utf-8", "replace")
                elif k == 5:
                    sname, svalue = _stat(x, stat_names)
                    stats[sname] = svalue
            meta[mid] = (ev_name, stats)
        lines = {}
        for raw in lines_raw:
            lname, t0_ps, events = "", 0, []
            for k, _, x in fields(raw):
                if k == 2:
                    lname = x.decode()
                elif k == 3:
                    t0_ps = x * 1000  # timestamp_ns
                elif k == 4:
                    mid = off = dur = 0
                    for ek, _, ex in fields(x):
                        if ek == 1:
                            mid = ex
                        elif ek == 2:
                            off = ex
                        elif ek == 3:
                            dur = ex
                    events.append((mid, off, dur))
            lines[lname] = [(m, t0_ps + o, d) for m, o, d in events]
        yield name, meta, lines


# ----------------------------------------------------------------- the table


def self_times(events):
    """``(metadata id, exclusive ps)``: an event's duration less the events
    nested in it, so that a loop and its body are not counted twice."""
    out, stack = [], []  # stack of [end, index into out]
    for mid, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        out.append([mid, dur])
        stack.append([start + dur, len(out) - 1])
    return out


def scope_of(tf_op: str | None) -> str:
    if not tf_op:
        return "(no tf_op)"
    m = SCOPE.search(tf_op)
    return m.group(1) if m else "(tf_op, no scope)"


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("trace", type=Path, help="a .xplane.pb or a directory "
                   "holding one (the newest is read)")
    p.add_argument("--top", type=int, default=6, help="operations a scope")
    args = p.parse_args()
    path = args.trace
    if path.is_dir():
        found = sorted(path.rglob("*.xplane.pb"))
        if not found:
            raise SystemExit(f"scope_dump: no .xplane.pb under {path}")
        path = found[-1]
    print(f"trace {path}")
    seen = False
    for name, meta, lines in planes(path):
        if not name.startswith("/device:") or "XLA Ops" not in lines:
            continue
        seen = True
        by_program = defaultdict(lambda: [0, 0])
        for mid, _, dur in lines.get("XLA Modules", ()):
            rec = by_program[meta.get(mid, ("?", {}))[0]]
            rec[0] += dur
            rec[1] += 1
        program, (_, steps) = max(by_program.items(), key=lambda kv: kv[1][0],
                                  default=("?", (0, 1)))
        by_scope = defaultdict(lambda: defaultdict(lambda: [0, 0]))
        calls = defaultdict(lambda: [0, 0])
        for mid, ps in self_times(lines["XLA Ops"]):
            ev_name, stats = meta.get(mid, ("?", {}))
            tf_op = stats.get("tf_op")
            rec = by_scope[scope_of(tf_op)][ev_name]
            rec[0] += ps
            rec[1] += 1
            if " custom-call(" in ev_name:  # the op itself, not an operand
                rec = calls[f"{ev_name.split(' = ')[0]}  tf_op={tf_op}"]
                rec[0] += ps
                rec[1] += 1
        total = sum(r[0] for ops in by_scope.values() for r in ops.values())
        print(f"\nplane {name!r}: {steps} executions of {program[:60]!r}; "
              f"{total / 1e9 / steps:.3f} device ms a step on 'XLA Ops'")
        print(f"  {'scope':<20}{'ms/step':>9}{'share':>8}")
        for scope, ops in sorted(by_scope.items(),
                                 key=lambda kv: -sum(r[0] for r in kv[1].values())):
            ps = sum(r[0] for r in ops.values())
            print(f"  {scope:<20}{ps / 1e9 / steps:9.3f}{100 * ps / total:7.1f}%")
            for ev_name, (ops_ps, n) in sorted(
                    ops.items(), key=lambda kv: -kv[1][0])[:args.top]:
                print(f"      {ops_ps / 1e9 / steps:8.3f} ms x{n / steps:<5.3g}"
                      f" {ev_name[:100]}")
        print("  custom calls (kernels):")
        for label, (ps, n) in sorted(calls.items(),
                                     key=lambda kv: -kv[1][0])[:args.top]:
            print(f"      {ps / 1e9 / steps:8.3f} ms x{n / steps:<5.3g} {label[:160]}")
    if not seen:
        raise SystemExit("scope_dump: no device plane with an 'XLA Ops' line")
    return 0


if __name__ == "__main__":
    sys.exit(main())
