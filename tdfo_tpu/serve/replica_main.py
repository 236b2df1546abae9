"""Replica process entry: ``python -m tdfo_tpu.serve.replica_main spec.json``.

One :class:`~tdfo_tpu.serve.fleet.ReplicaFrontend` behind an ``AF_UNIX``
listener, speaking the ``serve/wire.py`` framed protocol.  The supervisor
(``serve/supervisor.py``) writes the spec file and spawns this module; the
ingress connects and drives it.  The process IS the replica: ``kill -9``
takes the scorer, the batcher, and the connection down with it, and the
respawned lineage proves the robustness bar — it re-reads the SAME spec,
re-follows ``CURRENT``/``CANARY`` by (version, digest) through the shared
:class:`~tdfo_tpu.serve.swap.BundleStore` (a pointer FOLLOWER — ``recover``
belongs to the one writer, the online supervisor), and reopens the SAME
``replica-<k>`` request-log directory, whose writer resumes seq-contiguously
by construction (``data/replay.RequestLog`` scans seals + active segment on
open).

Startup: the supervisor binds the listener BEFORE spawning and passes it
down as ``--listen-fd`` (socket activation), because ``python -m``
resolves the package — jax included — before ``main`` runs: on a loaded
single-core box that import takes minutes, far past any sane
connect-retry budget.  With the fd handoff the ingress's connect lands
in the kernel backlog at spawn time and the first RPC simply blocks
until the replica has imported, synced, and called ``accept``.  Run
manually (no ``--listen-fd``), the child binds for itself and the
ingress's connect-retry schedule (``[serving] connect_retries`` x
``connect_base_ms`` through the single ``utils/retry.backoff_delay``
law) covers the import window instead.

Spec keys: ``replica_id``, ``socket`` (listener path), ``store_dir``,
``serving`` (a ``[serving]`` dict), ``canary_member``, ``request_log_root``
(optional), ``trace_dir`` (optional — spans append to the SHARED sinks;
``obs/trace.emit`` writes one complete line per record so concurrent
multi-process appends never tear), ``slow_score_ms`` (the only fault a
replica child honours — kill faults belong to the parent), and
``jax_platforms`` (default ``"cpu"``).  A chip belongs to one process and
the parent has it, so children score on the CPU until ROADMAP.md S4 moves a
scorer onto the chip.  The platform cannot be chosen in here: ``python -m``
imports the package, and with it jax, before ``main`` runs, and jax reads
``JAX_PLATFORMS`` once at import.  The supervisor therefore puts it into the
child's environment (``Popen(env=...)``), and ``main`` only checks that
what jax read is what the spec says, and dies otherwise.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Any


def _serve(spec: dict[str, Any], listener) -> None:
    import select

    import numpy as np

    from tdfo_tpu.core.config import ServingSpec
    from tdfo_tpu.obs import trace as _trace
    from tdfo_tpu.serve import wire
    from tdfo_tpu.serve.fleet import ReplicaFrontend
    from tdfo_tpu.serve.swap import BundleStore
    from tdfo_tpu.train.metrics import binary_auc
    from tdfo_tpu.utils import faults as _faults

    if spec.get("trace_dir"):
        _trace.configure(spec["trace_dir"])
    slow_ms = float(spec.get("slow_score_ms") or 0.0)
    if slow_ms:
        _faults.configure(_faults.FaultSpec(slow_score_ms=slow_ms))

    serving_raw = dict(spec["serving"])
    serving_raw["buckets"] = tuple(serving_raw["buckets"])
    serving = ServingSpec(**serving_raw)
    max_frame = serving.max_frame_bytes
    replica_id = int(spec["replica_id"])

    store = BundleStore(spec["store_dir"])  # follower: no recover()
    replica = ReplicaFrontend(
        replica_id, store, serving, mesh=None,
        request_log_root=spec.get("request_log_root"),
        canary_member=bool(spec.get("canary_member", False)))

    warmed: set[Any] = set()
    poll_s = max(serving.batch_deadline_ms / 1000.0, 0.001)

    def flush_replies(conn, pending: set) -> None:
        # every completed rid (scored or shed) answers exactly once, and
        # carries the batcher's queue state so score replies double as
        # balance observations at the ingress
        b = replica.batcher
        if b is None:
            return
        for rid in [r for r in list(b.results) if r in pending]:
            scores = b.results.pop(rid)
            pending.discard(rid)
            wire.send_msg(conn, {
                "type": "reply", "rid": rid,
                "scores": None if scores is None
                else np.asarray(scores, np.float32).ravel().tolist(),
                "queue_depth": b.last_queue_depth,
                "batch_fill": b.last_batch_fill,
            }, max_frame=max_frame)

    def handle(conn, msg: dict[str, Any], pending: set) -> bool:
        """Dispatch one message; False ends the process."""
        kind = msg.get("type")
        if kind == "score":
            replica.batcher.submit(msg["rid"], wire.decode_feats(msg["feats"]))
            pending.add(msg["rid"])
            replica.batcher.poll()
            flush_replies(conn, pending)
        elif kind == "sync":
            version = replica.sync(frozenset(msg.get("skew") or ()),
                                   frozenset(msg.get("slow") or ()))
            served = replica._served
            wire.send_msg(conn, {
                "type": "synced", "replica": replica_id, "version": version,
                "digest": None if served is None else served[1],
            }, max_frame=max_frame)
        elif kind == "heartbeat":
            feats = wire.decode_feats(msg["feats"])
            labels = np.asarray(msg["labels"])
            if replica._served not in warmed:
                # unmeasured warm-up, mirroring ServingFleet.heartbeat: jit
                # compilation is a one-time cost that would otherwise show
                # up as a per-cycle canary p99 regression
                warmed.add(replica._served)
                replica.score_direct({k: np.array(v)
                                      for k, v in feats.items()})
            t0 = _trace.clock()
            scores = replica.score_direct({k: np.array(v)
                                           for k, v in feats.items()})
            ms = _trace.elapsed_ms(t0)
            rec: dict[str, Any] = {
                "type": "heartbeat_reply", "replica": replica_id,
                "version": replica.version(),
                "auc": float(binary_auc(labels, scores)), "ms": ms,
                "canary": replica.canary_member,
            }
            if replica.batcher is not None:
                rec["queue_depth"] = replica.batcher.last_queue_depth
                rec["batch_fill"] = replica.batcher.last_batch_fill
            wire.send_msg(conn, rec, max_frame=max_frame)
        elif kind == "probe":
            trace = [(rid, wire.decode_feats(enc))
                     for rid, enc in msg["requests"]]
            results = replica.batcher.run(trace)
            pending.difference_update(results)
            wire.send_msg(conn, {
                "type": "probed", "replica": replica_id,
                "results": {str(rid): None if v is None
                            else np.asarray(v, np.float32).ravel().tolist()
                            for rid, v in results.items()},
            }, max_frame=max_frame)
        elif kind == "drain":
            if replica.batcher is not None:
                replica.batcher.drain()
            flush_replies(conn, pending)
            wire.send_msg(conn, {"type": "drained", "replica": replica_id},
                          max_frame=max_frame)
        elif kind == "shutdown":
            wire.send_msg(conn, {"type": "bye", "replica": replica_id},
                          max_frame=max_frame)
            return False
        else:
            raise wire.WireError(f"unknown message type {kind!r}")
        return True

    running = True
    while running:
        conn, _ = listener.accept()  # one ingress connection at a time
        pending: set = set()
        try:
            while True:
                readable, _, _ = select.select([conn], [], [], poll_s)
                if not readable:
                    # deadline tick: ship expired partial batches, answer
                    # their waiters
                    if replica.batcher is not None:
                        replica.batcher.poll()
                        flush_replies(conn, pending)
                    continue
                if not handle(conn, wire.recv_msg(conn, max_frame=max_frame),
                              pending):
                    running = False
                    break
        except wire.Disconnect:
            pass  # ingress went away; drop state, wait for a reconnect
        except wire.WireError as e:
            print(f"[replica {replica_id}] wire error: {e}", file=sys.stderr,
                  flush=True)
        finally:
            try:
                conn.close()
            except OSError:
                pass
    replica.close()


def main() -> None:
    spec = json.loads(Path(sys.argv[1]).read_text())
    import jax

    want = str(spec.get("jax_platforms", "cpu"))
    got = jax.config.jax_platforms
    if got != want:
        # jax is already imported (module docstring): assigning the variable
        # here would change nothing, and a child that reached for the chip
        # its parent holds would fail or hang
        raise SystemExit(
            f"[replica {spec.get('replica_id')}] jax_platforms is {got!r} "
            f"but the spec says {want!r}: start this process with "
            f"JAX_PLATFORMS={want} in its environment (the supervisor does)")
    print(f"[replica {spec.get('replica_id')}] jax_platforms={got}",
          flush=True)

    from tdfo_tpu.serve import wire

    if "--listen-fd" in sys.argv:
        # socket activation: adopt the supervisor's pre-bound listener —
        # its backlog has been accepting connects since before this
        # interpreter existed
        fd = int(sys.argv[sys.argv.index("--listen-fd") + 1])
        listener = wire.listener_from_fd(fd)
    else:
        # manual run: bind here — the ingress can still connect (and
        # queue its first RPC in the backlog) while the scorer jits
        listener = wire.listen(spec["socket"])
    try:
        _serve(spec, listener)
    finally:
        listener.close()
        Path(spec["socket"]).unlink(missing_ok=True)


if __name__ == "__main__":
    main()
