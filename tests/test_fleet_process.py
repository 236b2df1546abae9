"""Out-of-process serving fleet tests (``tdfo_tpu/serve/supervisor.py`` +
``serve/ingress.py`` + ``serve/loadgen.py``).

Three layers:

* **Unit** (tier 1, no processes): the ingress's power-of-two-choices
  balance and heartbeat-staleness eviction under an injected
  ``elapsed_ms`` (the PR-16 heartbeat fix: a stalled replica must stop
  receiving traffic within one eviction window), the supervisor's
  respawn-backoff schedule and flap quarantine under injected
  popen/clock/sleep/rng, and the load generator's closed/open arrival
  disciplines against a fake ingress — no wall-clock sleeps anywhere.

* **Acceptance** (tier 1, real processes): the gated online loop with
  ``[serving] fleet_mode = "process"`` — replicas are real OS processes
  behind the socket ingress — SIGKILLed mid-canary-watch
  (``[faults] kill_replica_signal``) versus the identical unkilled
  process-mode run.  The supervisor must respawn the victim, the
  respawned lineage must re-follow ``CURRENT``/``CANARY`` by
  (version, digest) and relearn every armed fault from the full-digest
  sync, and the verdicts / store state / per-replica probe logits must
  converge BITWISE to the unkilled reference.

* **Slow matrix**: the canary-rollback drill across the RPC boundary,
  permanent quarantine (``kill_replica_nth``) degrading the fleet, and a
  standalone mini-fleet proving the per-replica request log resumes
  seq-contiguously across a SIGKILL + respawn, with the load generator
  driving the same live fleet.
"""

import json
import os
import random
import signal
import socket
from pathlib import Path

import numpy as np
import pytest

from test_fleet import (  # noqa: F401  (fleet_env is a fixture)
    N_CYCLES,
    N_REPLICAS,
    _events,
    _make_spec,
    _run_worker,
    _run_workers,
    fleet_env,
)

from tdfo_tpu.serve import wire
from tdfo_tpu.serve.ingress import Ingress
from tdfo_tpu.serve.supervisor import ProcessSupervisor
from tdfo_tpu.utils.retry import backoff_delay


class _Recorder:
    """Duck-typed logger: collects ``log(**kw)`` records."""

    def __init__(self):
        self.events = []

    def log(self, **kw):
        self.events.append(kw)


# ------------------------------------------------- ingress balance + eviction


def _bare_ingress(stale_ms=100.0, seed=0, **kw):
    """An Ingress with no real connections: ``elapsed_ms`` is injected as
    the IDENTITY, so tests write ``hb_at`` stamps that are literally the
    observation's age in milliseconds."""
    return Ingress({}, stale_ms=stale_ms, rng=random.Random(seed),
                   elapsed_ms=lambda hb_at: hb_at, **kw)


def _stat(ing, k, age_ms, depth=0, fill=0.0):
    ing._stats[k] = {"queue_depth": depth, "batch_fill": fill,
                     "hb_at": float(age_ms)}


def test_ingress_evicts_stale_heartbeats_within_one_window():
    """The PR-16 heartbeat-staleness regression: a replica whose last
    observation is older than ``[serving] heartbeat_stale_ms`` stops
    receiving requests immediately — it used to keep its last
    ``queue_depth`` forever and kept winning the balance after death."""
    ing = _bare_ingress(stale_ms=100.0)
    ing._conns = {0: object(), 1: object()}
    _stat(ing, 0, age_ms=10.0, depth=5)
    _stat(ing, 1, age_ms=10.0, depth=0)
    assert ing.fresh() == [0, 1]
    assert {ing.pick() for _ in range(20)} == {1}  # less loaded wins

    # replica 1 stalls holding the WINNING queue_depth — the exact shape
    # the fix targets: a dead replica's frozen stats used to keep beating
    # the balance forever
    _stat(ing, 1, age_ms=150.0, depth=0)
    assert ing.fresh() == [0]
    assert {ing.pick() for _ in range(20)} == {0}

    # the whole fleet stale is a LOUD error, never a silent route-to-dead
    _stat(ing, 0, age_ms=101.0)
    with pytest.raises(RuntimeError, match="no fresh replica"):
        ing.pick()


def test_ingress_p2c_prefers_less_loaded():
    """Power-of-two-choices over (queue_depth, batch_fill, id): with two
    replicas both samples always land, so the ordering is exact."""
    ing = _bare_ingress()
    ing._conns = {0: object(), 1: object()}
    _stat(ing, 0, age_ms=0.0, depth=6)
    _stat(ing, 1, age_ms=0.0, depth=0)
    assert {ing.pick() for _ in range(20)} == {1}
    _stat(ing, 0, age_ms=0.0, depth=2, fill=0.9)
    _stat(ing, 1, age_ms=0.0, depth=2, fill=0.1)
    assert {ing.pick() for _ in range(20)} == {1}  # depth tie -> lower fill
    _stat(ing, 1, age_ms=0.0, depth=2, fill=0.9)
    assert {ing.pick() for _ in range(20)} == {0}  # full tie -> lower id


def test_ingress_rpc_folds_interleaved_score_replies():
    """Drain-on-swap ordering at the wire level: score replies that land
    before the drain acknowledgment are folded into ``completed`` (shed
    = ``null`` scores counted), and the rpc returns the control reply."""
    ours, theirs = socket.socketpair()
    try:
        ing = _bare_ingress(stale_ms=1e9)
        ing._conns[0] = ours
        ing._inflight["r9"] = (0, 123.0)
        ing._inflight["r10"] = (0, 5.0)
        wire.send_msg(theirs, {"type": "reply", "rid": "r9",
                               "scores": [0.5, 2.0], "queue_depth": 3,
                               "batch_fill": 0.75})
        wire.send_msg(theirs, {"type": "reply", "rid": "r10",
                               "scores": None, "queue_depth": 2,
                               "batch_fill": 0.5})
        wire.send_msg(theirs, {"type": "drained", "replica": 0})
        reply = ing.rpc(0, {"type": "drain"})
        assert reply == {"type": "drained", "replica": 0}
        np.testing.assert_array_equal(ing.completed["r9"],
                                      np.asarray([0.5, 2.0], np.float32))
        assert ing.completed["r10"] is None
        assert ing.sheds == 1
        assert ing.latencies_ms == [123.0]  # identity elapsed_ms: the stamp
        # score replies double as balance observations
        assert ing._stats[0]["queue_depth"] == 2
        assert wire.recv_msg(theirs) == {"type": "drain"}
    finally:
        ours.close()
        theirs.close()


def test_ingress_disconnect_fails_inflight_loudly():
    """Requests in flight on a dying connection land as ``None`` in
    ``completed`` with the failure counted and ledgered — never silently
    dropped (the caller would hang waiting for them)."""
    ours, theirs = socket.socketpair()
    log = _Recorder()
    try:
        ing = _bare_ingress(logger=log)
        ing._conns[0] = ours
        ing._inflight["lost1"] = (0, 0.0)
        ing._inflight["lost2"] = (0, 0.0)
        ing.disconnect(0)
        assert ing.completed == {"lost1": None, "lost2": None}
        assert ing.failures == 2
        assert ing.inflight() == 0
        assert log.events == [{"event": "ingress_inflight_lost",
                               "replica": 0, "requests": 2}]
    finally:
        theirs.close()


# ------------------------------------------------- supervisor respawn + flap


class _FakeProc:
    def __init__(self, pid):
        self.pid = pid
        self.returncode = None

    def poll(self):
        return self.returncode


def _fake_supervisor(**kw):
    spawned = []

    def popen(spec_path):
        proc = _FakeProc(pid=1000 + len(spawned))
        spawned.append(proc)
        return proc

    clock = {"t": 0.0}
    slept = []
    sup = ProcessSupervisor(
        {0: "/dev/null"}, sleep=slept.append, clock=lambda: clock["t"],
        rng=random.Random(7), popen=popen, **kw)
    return sup, spawned, slept, clock


def test_supervisor_backoff_schedule_and_flap_quarantine():
    """Respawn delays follow the single ``utils/retry.backoff_delay`` law
    bit-for-bit (capped exponential, injected rng), and the third death
    inside the flap window quarantines instead of respawning — loudly."""
    log = _Recorder()
    sup, spawned, slept, clock = _fake_supervisor(
        respawn_base_ms=50.0, respawn_max_ms=400.0, flap_window_s=30.0,
        flap_max_deaths=3, logger=log)
    sup.spawn_all()
    assert sup.alive_ids() == [0] and len(spawned) == 1

    spawned[-1].returncode = 9
    clock["t"] = 1.0
    assert sup.check() == [0]
    spawned[-1].returncode = 9
    clock["t"] = 2.0
    assert sup.check() == [0]
    assert sup.respawns == {0: 2} and len(spawned) == 3

    ref = random.Random(7)
    assert slept == [backoff_delay(i, base_delay=0.050, max_delay=0.400,
                                   rng=ref) for i in range(2)]

    spawned[-1].returncode = 9
    clock["t"] = 3.0
    assert sup.check() == []  # third death in the window: quarantined
    assert sup.quarantined == {0}
    assert len(spawned) == 3 and len(slept) == 2  # no fourth spawn, no sleep
    with pytest.raises(RuntimeError, match="quarantined"):
        sup.spawn(0)

    deaths = [e for e in log.events if e["event"] == "replica_died"]
    assert [e["deaths_in_window"] for e in deaths] == [1, 2, 3]
    assert [e["event"] for e in log.events].count("replica_quarantined") == 1


def test_supervisor_window_expiry_and_mark_healthy():
    """Deaths spaced wider than ``flap_window_s`` never quarantine, and
    ``mark_healthy`` (a respawned replica answered an RPC) resets the
    consecutive-death backoff to the base delay."""
    sup, spawned, slept, clock = _fake_supervisor(
        respawn_base_ms=50.0, respawn_max_ms=400.0, flap_window_s=30.0,
        flap_max_deaths=2)
    sup.spawn_all()
    for t in (0.0, 100.0, 200.0):  # each death alone in its window
        spawned[-1].returncode = 9
        clock["t"] = t
        assert sup.check() == [0]
        sup.mark_healthy(0)
    assert not sup.quarantined
    assert sup.respawns == {0: 3}
    ref = random.Random(7)
    expected = [backoff_delay(0, base_delay=0.050, max_delay=0.400, rng=ref)
                for _ in range(3)]
    assert slept == expected  # backoff index pinned at 0 by mark_healthy


def test_spawn_prebinds_listener_and_detaches_child_stdio(
        tmp_path, monkeypatch):
    """The socket-activation + stdio-hygiene contract of the REAL spawn
    path (``_spawn_child``), with ``Popen`` faked out:

    * the socket accepts a connection BEFORE any child process exists —
      a child spending a minute importing jax on a loaded single-core
      box can no longer outlast the ingress's connect-retry budget (the
      regression that wedged the tier-1 suite);
    * the bound listener fd rides down via ``--listen-fd`` + ``pass_fds``;
    * child stdio is the per-replica log file + DEVNULL stdin, never an
      inherited pipe — an orphaned child must not be able to hold a test
      harness's ``communicate()`` open after the parent dies.
    """
    import subprocess as sp

    sock = tmp_path / "replica-0.sock"
    spec = tmp_path / "replica-0.json"
    spec.write_text(json.dumps({"replica_id": 0, "socket": str(sock)}))

    calls = []
    inherited = []

    def fake_popen(argv, **kw):
        # what fork+exec under pass_fds does for a real child: duplicate
        # the fd so it outlives the parent's listener.close()
        inherited.extend(os.dup(fd) for fd in kw.get("pass_fds", ()))
        calls.append((argv, kw))
        return _FakeProc(4242)

    monkeypatch.setattr(sp, "Popen", fake_popen)
    proc = ProcessSupervisor._spawn_child(spec)
    assert isinstance(proc, _FakeProc)
    (argv, kw), = calls
    fd = int(argv[argv.index("--listen-fd") + 1])
    assert kw["pass_fds"] == (fd,)
    assert kw["stdin"] is sp.DEVNULL
    assert kw["stdout"].name == str(tmp_path / "replica-0.log")
    assert kw["stderr"] is kw["stdout"]

    # no child process exists (Popen was fake) and the parent has already
    # closed its listener copy, yet the path connects instantly: the
    # pre-bound socket's backlog — kept alive by the "inherited" fd — is
    # holding the connection
    client = wire.connect(sock, attempts=1)
    adopted = wire.listener_from_fd(inherited.pop())
    try:
        conn, _ = adopted.accept()
        wire.send_msg(conn, {"type": "synced"})
        assert wire.recv_msg(client) == {"type": "synced"}
        conn.close()
    finally:
        client.close()
        adopted.close()


def _replica_spec(tmp_path):
    import dataclasses

    from tdfo_tpu.core.config import ServingSpec

    sock = tmp_path / "replica-0.sock"
    spec = tmp_path / "replica-0.json"
    spec.write_text(json.dumps({
        "replica_id": 0, "socket": str(sock),
        "store_dir": str(tmp_path / "store"),
        "serving": dataclasses.asdict(ServingSpec()),
        "jax_platforms": "cpu"}))
    return sock, spec


def test_spawned_replica_is_pinned_to_cpu_without_parent_env(
        tmp_path, monkeypatch):
    """One process per chip: on the chip machine ``JAX_PLATFORMS`` is unset
    and the parent holds the chip, so the REAL spawn path must put the
    spec's platform into the child's environment before the interpreter
    starts (``python -m`` imports jax before ``replica_main.main`` runs — a
    later assignment is not read).  The child reports what jax read."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    sock, spec = _replica_spec(tmp_path)
    proc = ProcessSupervisor._spawn_child(spec)
    try:
        # the pre-bound listener queues this until the child has imported
        # jax, passed its platform check and called accept
        client = wire.connect(sock, attempts=1)
        try:
            wire.send_msg(client, {"type": "shutdown"})
            assert wire.recv_msg(client) == {"type": "bye", "replica": 0}
        finally:
            client.close()
        assert proc.wait(timeout=120) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
    log = (tmp_path / "replica-0.log").read_text()
    assert "[replica 0] jax_platforms=cpu" in log, log


def test_replica_refuses_a_platform_its_spec_does_not_name(tmp_path):
    """Started by hand without the variable, jax has read no platform and
    would reach for whatever accelerator is there: the child must die at
    its check, before any backend initialises."""
    import subprocess as sp
    import sys

    _, spec = _replica_spec(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    out = sp.run([sys.executable, "-m", "tdfo_tpu.serve.replica_main",
                  str(spec)], env=env, capture_output=True, text=True,
                 timeout=120, cwd=Path(__file__).resolve().parents[1])
    assert out.returncode != 0
    assert "jax_platforms is None but the spec says 'cpu'" in out.stderr
    assert "jax_platforms=" not in out.stdout


# ---------------------------------------------------- loadgen disciplines


class _FakeIngress:
    """The duck-typed submit/poll surface: completes one request per poll
    at a fixed latency, records the high-water inflight mark."""

    def __init__(self, latency_ms=5.0, clock=None):
        self.completed = {}
        self.latencies_ms = []
        self.sheds = 0
        self.failures = 0
        self._queue = []
        self._latency_ms = latency_ms
        self._clock = clock
        self.max_inflight = 0

    def submit(self, rid, feats):
        self._queue.append(rid)
        self.max_inflight = max(self.max_inflight, len(self._queue))
        return 0

    def inflight(self):
        return len(self._queue)

    def poll(self, timeout_s=0.0):
        if self._clock is not None:
            self._clock["ms"] += 1.0  # a poll IS the passage of time here
        if not self._queue:
            return 0
        rid = self._queue.pop(0)
        self.completed[rid] = np.zeros(1, np.float32)
        self.latencies_ms.append(self._latency_ms)
        return 1


def test_loadgen_request_is_zipf_in_vocab():
    from tdfo_tpu.core.config import LoadgenSpec
    from tdfo_tpu.serve.loadgen import LoadGenerator

    spec = LoadgenSpec(rows_per_request=64, seed=3, zipf_a=2.0)
    gen = LoadGenerator(_FakeIngress(), spec,
                        {"user_id": 50, "item_id": 7}, ("avg_rating",))
    rids = set()
    for _ in range(4):
        rid, batch = gen.request()
        rids.add(rid)
        assert batch["user_id"].dtype == np.int32
        assert batch["user_id"].shape == (64,)
        assert batch["user_id"].min() >= 0 and batch["user_id"].max() < 50
        assert batch["item_id"].max() < 7
        assert batch["avg_rating"].dtype == np.float32
    assert len(rids) == 4  # serial rids never collide
    # zipf head-heaviness: rank-0 ids dominate a uniform draw's share
    big = gen.request()[1]["user_id"]
    assert (big == 0).mean() > 0.3


def test_loadgen_closed_loop_respects_concurrency():
    from tdfo_tpu.core.config import LoadgenSpec
    from tdfo_tpu.serve.loadgen import LoadGenerator

    ing = _FakeIngress(latency_ms=5.0)
    spec = LoadgenSpec(mode="closed", requests=10, concurrency=3,
                       rows_per_request=2, p99_slo_ms=50.0)
    gen = LoadGenerator(ing, spec, {"user_id": 8})
    stats = gen.run()
    assert stats["mode"] == "closed"
    assert stats["offered"] == 10 and stats["completed"] == 10
    assert stats["concurrency"] == 3 and stats["offered_qps"] is None
    assert ing.max_inflight <= 3  # replies fund sends; never over-admits
    assert stats["p50_ms"] == 5.0 and stats["p99_ms"] == 5.0
    assert stats["slo_ok"] is True and stats["shed"] == 0


def test_loadgen_open_loop_paces_by_rate_not_replies():
    """Open loop submits on the arrival schedule whether or not replies
    came back — the discipline that can see past saturation.  Time is a
    fake millisecond counter advanced by ingress polls, so the pacing
    math runs without wall-clock sleeps."""
    from tdfo_tpu.core.config import LoadgenSpec
    from tdfo_tpu.serve.loadgen import LoadGenerator

    clock = {"ms": 0.0}
    ing = _FakeIngress(latency_ms=5.0, clock=clock)
    spec = LoadgenSpec(mode="open", requests=8, rate_qps=100.0,
                       rows_per_request=2, p99_slo_ms=50.0)
    gen = LoadGenerator(ing, spec, {"user_id": 8},
                        elapsed_ms=lambda t0: clock["ms"])
    stats = gen.run()
    assert stats["mode"] == "open"
    assert stats["offered_qps"] == 100.0 and stats["concurrency"] is None
    assert stats["completed"] == 8 and stats["failed"] == 0
    # 8 arrivals at 10 ms spacing: the wall is the schedule, not the sum
    # of service times
    assert clock["ms"] >= 70.0
    assert stats["achieved_qps"] > 0


def test_loadgen_knee_doubles_the_load_axis():
    from tdfo_tpu.core.config import LoadgenSpec
    from tdfo_tpu.serve.loadgen import LoadGenerator

    ing = _FakeIngress(latency_ms=5.0)
    spec = LoadgenSpec(mode="closed", requests=6, rows_per_request=2,
                       p99_slo_ms=50.0)
    gen = LoadGenerator(ing, spec, {"user_id": 8})
    report = gen.knee(steps=3)
    assert [r["concurrency"] for r in report["steps"]] == [1, 2, 4]
    assert all(r["slo_ok"] for r in report["steps"])
    assert report["knee"] is report["steps"][-1]  # last SLO-meeting step


# ------------------------------------------- tier-1 process-fleet acceptance


@pytest.fixture(scope="module")
def proc_runs(fleet_env, tmp_path_factory):
    """Two concurrent gated runs with ``fleet_mode = "process"``:

    * ``procref`` — fault-free: the unkilled reference.
    * ``prockill`` — ``kill_replica_signal = 1``: replica 0 (the canary
      member) takes a real SIGKILL at the first canary-watch round; the
      supervisor must respawn it before the verdict heartbeats.
    """
    tmp = tmp_path_factory.mktemp("proc_runs")
    ref_p = _make_spec(tmp, fleet_env, "procref", ckpt="ckpt_ref",
                       log="log_ref", fleet_mode="process",
                       telemetry={"trace": True})
    kill_p = _make_spec(tmp, fleet_env, "prockill", ckpt="ckpt_kill",
                        log="log_kill", fleet_mode="process",
                        telemetry={"trace": True},
                        faults={"kill_replica_signal": 1})
    rcs, outs = _run_workers([ref_p, kill_p])
    assert rcs[0] == 0, f"procref failed rc={rcs[0]}\n{outs[0][-2000:]}"
    assert rcs[1] == 0, f"prockill failed rc={rcs[1]}\n{outs[1][-2000:]}"
    return dict(
        ref=json.loads((tmp / "procref.json").read_text()),
        kill=json.loads((tmp / "prockill.json").read_text()),
        ref_metrics=tmp / "log_ref" / "metrics.jsonl",
        kill_metrics=tmp / "log_kill" / "metrics.jsonl",
    )


def test_sigkill_respawn_converges_bitwise(proc_runs):
    """The PR-16 robustness bar: SIGKILL a replica process mid-watch ->
    supervisor respawns it -> the respawned lineage re-follows
    CURRENT/CANARY by (version, digest) -> the gated run's store state,
    replay cursor, verdicts, and per-replica probe logits are BITWISE
    identical to the unkilled process-mode reference."""
    ref, kd = proc_runs["ref"], proc_runs["kill"]
    assert int(kd["respawns"].get("0", 0)) >= 1  # the victim really died
    assert all(int(v) == 0 for v in ref["respawns"].values())
    assert kd["dead_replicas"] == []  # respawned, never quarantined
    assert ref["dead_replicas"] == []
    for key in ("version", "digest", "cursor", "cycles_done",
                "replica_versions", "rejections", "logits"):
        assert kd[key] == ref[key], key


def test_sigkill_drill_is_ledgered(proc_runs):
    """The kill and the death are both ledgered events (a drill that
    leaves no trace proves nothing), the returncode is the signal, and
    every cycle still promoted in BOTH runs."""
    sigkills = _events(proc_runs["kill_metrics"], "replica_sigkilled")
    assert [e["replica"] for e in sigkills] == [0]
    died = _events(proc_runs["kill_metrics"], "replica_died")
    assert died and died[0]["replica"] == 0
    assert died[0]["returncode"] == -int(signal.SIGKILL)
    assert not _events(proc_runs["ref_metrics"], "replica_died")
    for key in ("ref_metrics", "kill_metrics"):
        cycles = _events(proc_runs[key], "online_cycle")
        assert [c["verdict"] for c in cycles] == ["promote"] * N_CYCLES, key


def test_process_replicas_agree_bitwise(proc_runs):
    """Both replica processes serve identical logits for the identical
    probe trace — the wire codec and the process boundary perturb
    nothing."""
    logits = proc_runs["ref"]["logits"]
    assert sorted(logits) == [str(k) for k in range(N_REPLICAS)]
    per_replica = [logits[k] for k in sorted(logits)]
    assert all(r == per_replica[0] for r in per_replica[1:])


# --------------------------------------------------------------- slow matrix


@pytest.mark.slow
def test_process_drill_rollback_over_rpc(fleet_env, tmp_path):
    """The canary-rollback drill across the RPC boundary: the skew digest
    rides the sync fan-out, only the canary CHILD PROCESS serves skewed
    logits, and the verdict sequence matches the in-process drill —
    rollback at cycle 1, promote at cycle 2, rejection ledgered."""
    spec = _make_spec(tmp_path, fleet_env, "procdrill", ckpt="ckpt",
                      log="log", fleet_mode="process",
                      faults={"regress_auc_at_cycle": 1})
    rc, out = _run_worker(spec)
    assert rc == 0, f"rc={rc}\n{out[-2000:]}"
    res = json.loads((tmp_path / "procdrill.json").read_text())
    cycles = _events(tmp_path / "log" / "metrics.jsonl", "online_cycle")
    assert [c["verdict"] for c in cycles] == ["rollback", "promote"]
    assert len(res["rejections"]) == 1
    assert res["rejections"][0]["version"] == cycles[0]["version"]
    assert res["dead_replicas"] == []


@pytest.mark.slow
def test_process_quarantine_degrades_fleet(fleet_env, tmp_path):
    """``kill_replica_nth = 2`` in process mode permanently quarantines
    the stable replica (the in-process soft-kill twin): membership stays
    degraded, no respawn, and the healthy candidate still promotes —
    exactly the in-process expectation for a stable-cohort death."""
    spec = _make_spec(tmp_path, fleet_env, "procq", ckpt="ckpt", log="log",
                      fleet_mode="process",
                      faults={"kill_replica_nth": 2})
    rc, out = _run_worker(spec)
    assert rc == 0, f"rc={rc}\n{out[-2000:]}"
    res = json.loads((tmp_path / "procq.json").read_text())
    assert res["dead_replicas"] == [1]
    assert all(int(v) == 0 for v in res["respawns"].values())
    assert sorted(res["replica_versions"]) == ["0"]  # survivors only
    assert res["version"] == N_CYCLES and res["rejections"] == []
    cycles = _events(tmp_path / "log" / "metrics.jsonl", "online_cycle")
    assert [c["verdict"] for c in cycles] == ["promote"] * N_CYCLES
    quarantines = _events(tmp_path / "log" / "metrics.jsonl",
                          "replica_quarantined")
    assert [e["replica"] for e in quarantines] == [1]


@pytest.mark.slow
def test_process_fleet_request_log_and_loadgen_survive_sigkill(mesh8,
                                                               tmp_path):
    """A standalone mini-fleet (no training loop): route traffic, SIGKILL
    a replica, respawn, route more — every request is answered, the
    victim's per-replica request log resumes SEQ-CONTIGUOUSLY across its
    death (segments rotate mid-run, so the resume crosses a seal
    boundary), and the load generator sweeps the same live fleet."""
    from test_serve_swap import CONT_COLS, SIZE_MAP, _batch, _export_kw, \
        _setup

    from tdfo_tpu.core.config import Config, LoadgenSpec, ServingSpec
    from tdfo_tpu.data.replay import replica_log_dir
    from tdfo_tpu.serve.export import export_bundle
    from tdfo_tpu.serve.loadgen import LoadGenerator
    from tdfo_tpu.serve.supervisor import ProcessFleet
    from tdfo_tpu.serve.swap import BundleStore

    coll, _, state, _ = _setup(mesh8)
    bdir = export_bundle(tmp_path / "b", step=0, version=0,
                         **_export_kw(coll, state))
    store = BundleStore(tmp_path / "store")
    store.ingest_full(bdir)
    cfg = Config().replace(
        serving=ServingSpec(replicas=2, fleet_mode="process",
                            log_features=True, log_segment_bytes=2048),
        loadgen=LoadgenSpec(mode="closed", requests=12, rows_per_request=4,
                            p99_slo_ms=60_000.0))

    def _seqs(k):
        d = replica_log_dir(tmp_path / "rl", k)
        return [json.loads(line)["seq"]
                for seg in sorted(d.glob("requests-*.jsonl"))
                for line in seg.read_text().splitlines()]

    rng = np.random.default_rng(17)
    fleet = ProcessFleet(store, cfg, workdir=tmp_path,
                         request_log_root=tmp_path / "rl")
    try:
        fleet.ingress._rng = random.Random(3)  # pin the P2C draws
        fleet.sync()
        out1 = fleet.run([(f"a{i}", _batch(rng, 6)) for i in range(16)])
        assert len(out1) == 16
        assert all(v is not None for v in out1.values())
        victim_before = len(_seqs(0))
        assert victim_before >= 1  # the victim served some of phase 1

        fleet.supervisor.kill(0)  # real SIGKILL, mid-fleet
        fleet.ingress.disconnect(0)
        fleet.sync()  # check() respawns + reconnects, then re-arms
        assert fleet.supervisor.respawns[0] == 1
        assert fleet.alive_ids() == [0, 1]

        # completed is cumulative at the ingress; check the new rids
        out2 = fleet.run([(f"b{i}", _batch(rng, 6)) for i in range(16)])
        assert all(out2[f"b{i}"] is not None for i in range(16))

        gen = LoadGenerator(fleet.ingress, cfg.loadgen,
                            {c: SIZE_MAP[f] for f, c in
                             {"user": "user_id", "item": "item_id",
                              "language": "language", "is_ebook": "is_ebook",
                              "format": "format", "publisher": "publisher",
                              "pub_decade": "pub_decade"}.items()},
                            CONT_COLS)
        report = gen.knee(steps=2)
        assert [r["concurrency"] for r in report["steps"]] == [1, 2]
        assert all(r["completed"] == 12 and r["failed"] == 0
                   for r in report["steps"])
        assert report["knee"] is not None  # generous SLO: the knee exists
    finally:
        fleet.close()

    seqs0, seqs1 = _seqs(0), _seqs(1)
    # contiguous from 1, no gap at the death, no dup after the respawn
    assert seqs0 == list(range(1, len(seqs0) + 1))
    assert seqs1 == list(range(1, len(seqs1) + 1))
    assert len(seqs0) > victim_before  # the respawned lineage kept writing
    assert len(seqs0) + len(seqs1) == 32 + 2 * 12
    # rotation actually happened: the resume crossed a sealed segment
    assert len(list(replica_log_dir(tmp_path / "rl", 0)
                    .glob("requests-*.jsonl"))) > 1
