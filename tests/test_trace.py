"""Causal tracing (``[telemetry] trace``): sinks, assembly, zero-cost pin.

The tentpole contracts (``tdfo_tpu/obs/trace.py`` + ``obs/aggregate.py``):

  * **Off is free** — unconfigured ``emit``/``span`` touch no files, and a
    traced train step's jaxpr is BYTE-identical with tracing on: spans are
    host-side emits at serve/replay/cycle boundaries, nothing rides the
    step program.
  * **Sinks are crash-safe JSONL** — one complete line per append, rotated
    through the shared ``utils/logrotate`` machinery; the assembler skips
    (never guesses at) a torn tail.
  * **Ids join causally** — a served request's ``(replica, seq)`` flows
    from the frontend span through the replay batch into the online-cycle
    span; ``assemble`` reconstructs the chain, computes freshness lag from
    the only cross-process clock (wall ``ts``), and dedups cycle spans by
    cycle number so a killed-and-redone cycle assembles exactly once.

The multi-process version of the exactly-once audit (kill-drill fleet runs)
lives in tests/test_fleet.py; this file owns the single-process semantics.
"""

import dataclasses
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from tdfo_tpu.obs import trace
from tdfo_tpu.obs.aggregate import (assemble, chrome_trace, format_report,
                                    load_spans, percentile)

SCHEMA = {"x": (np.int32, ()), "y": (np.float32, ()),
          "label": (np.int8, ())}


@pytest.fixture(autouse=True)
def _detach_trace():
    yield
    trace.configure(None)


# ------------------------------------------------------------ sink basics


def test_emit_off_is_noop(tmp_path):
    assert not trace.active()
    trace.emit("frontend", "serve_request", seq=1)
    with trace.span("online", "stage", cycle=1) as extra:
        extra["verdict"] = "promote"
    assert list(tmp_path.iterdir()) == []  # nothing anywhere
    assert load_spans(tmp_path) == []


def test_emit_writes_complete_lines_and_load_spans_orders(tmp_path):
    trace.configure(tmp_path)
    trace.emit("frontend", "serve_request", replica=0, seq=1)
    trace.emit("replay", "replay_batch", rows=4)
    trace.emit("frontend", "serve_request", replica=0, seq=2)
    spans = load_spans(tmp_path)
    assert [s["span"] for s in spans] == [1, 2, 3]  # ts+id order
    assert (tmp_path / "trace-frontend.jsonl").exists()
    assert (tmp_path / "trace-replay.jsonl").exists()
    for p in tmp_path.glob("trace-*.jsonl"):
        for line in p.read_text().splitlines():
            json.loads(line)  # every line complete


def test_trace_sink_rotates_at_size(tmp_path):
    trace.configure(tmp_path, rotate_bytes=400)
    for i in range(40):
        trace.emit("frontend", "serve_request", replica=0, seq=i)
    main = tmp_path / "trace-frontend.jsonl"
    overflow = tmp_path / "trace-frontend.jsonl.1"
    assert overflow.exists()
    # the live file is bounded (absent right after a rotation, until the
    # next emit recreates it — the retries.jsonl shape)
    if main.exists():
        assert main.stat().st_size < 400 + 200
    # one generation of history is the contract: the survivors are a
    # contiguous, complete, ordered SUFFIX of the emitted spans
    seqs = [s["seq"] for s in load_spans(tmp_path)]
    assert seqs == list(range(seqs[0], 40))


def test_span_ids_deterministic_across_reconfigure(tmp_path):
    trace.configure(tmp_path / "a")
    for i in range(3):
        trace.emit("online", "stage", stage=f"s{i}")
    ids_a = [s["span"] for s in load_spans(tmp_path / "a")]
    trace.configure(tmp_path / "b")  # a restarted run
    for i in range(3):
        trace.emit("online", "stage", stage=f"s{i}")
    ids_b = [s["span"] for s in load_spans(tmp_path / "b")]
    assert ids_a == ids_b == [1, 2, 3]  # counter, never uuid/random


def test_span_contextmanager_emits_dur_even_on_raise(tmp_path):
    trace.configure(tmp_path)
    with pytest.raises(RuntimeError):
        with trace.span("online", "stage", cycle=2, stage="train") as extra:
            extra["steps"] = 5
            raise RuntimeError("killed mid-stage")
    (s,) = load_spans(tmp_path)
    assert s["kind"] == "stage" and s["stage"] == "train"
    assert s["steps"] == 5 and s["dur_ms"] >= 0.0


def test_load_spans_skips_torn_tail(tmp_path):
    trace.configure(tmp_path)
    trace.emit("replay", "replay_batch", rows=4)
    with open(tmp_path / "trace-replay.jsonl", "a") as f:
        f.write('{"span": 2, "ts": 1.0, "compo')  # kill mid-append
    spans = load_spans(tmp_path)
    assert len(spans) == 1 and spans[0]["rows"] == 4


# ------------------------------------------------------------- percentile


def test_percentile_nearest_rank():
    assert percentile([], 99) is None
    assert percentile([7.0], 50) == 7.0
    assert percentile([1, 2, 3, 4], 50) == 2.0  # nearest-rank, not interp
    samples = list(range(1, 101))
    assert percentile(samples, 99) == 99
    assert percentile(samples, 100) == 100
    assert percentile(samples, 0) == 1


# -------------------------------------------------------- causal assembly


def _cycle_span(cycle, *, version, verdict="promote", consumed=(),
                reason=None, digest="d0"):
    trace.emit("online", "online_cycle", cycle=cycle, verdict=verdict,
               reason=reason, version=version, digest=digest,
               step_begin=(cycle - 1) * 4, step_end=cycle * 4,
               dur_ms=12.5, consumed=[list(c) for c in consumed])


def test_end_to_end_id_chain(tmp_path):
    """Frontend serve spans -> replay batch spans -> a synthetic cycle span:
    ``assemble`` joins them on domain ids and computes freshness lag."""
    from tdfo_tpu.data.replay import ReplayConsumer, RequestLog
    from tdfo_tpu.serve.frontend import MicroBatcher

    trace.configure(tmp_path / "trace")
    log = RequestLog(tmp_path / "rl")
    mb = MicroBatcher(lambda b: np.asarray(b["x"], np.float32) * 2.0,
                      buckets=(8,), max_batch=8, batch_deadline_ms=0.0,
                      request_log=log)
    for i in range(4):
        mb.run([(f"q{i}", {
            "x": np.arange(i * 2, i * 2 + 2, dtype=np.int32),
            "y": np.full(2, 0.5, np.float32),
            "label": np.ones(2, np.int8)})])
    log.close()

    c = ReplayConsumer(tmp_path / "rl", schema=SCHEMA, batch_size=4)
    consumed = []
    while (out := c.next_batch()) is not None:
        consumed.extend(out[1])
    _cycle_span(1, version=7, consumed=consumed)
    # the produced version goes live on a replica (what lag is measured to)
    trace.emit("fleet", "replica_sync", replica=0, version=7, digest="d0",
               canary=False, skewed=False, slow=False)

    report = assemble(load_spans(tmp_path / "trace"))
    assert report["n_requests"] == 4 and report["n_replay_batches"] == 2
    (cyc,) = report["cycles"]
    assert cyc["verdict"] == "promote" and cyc["version"] == 7
    # flat single-log consumer -> replica 0 join keys, matching the
    # single frontend's spans; seqs are the log's own 1-based numbers
    assert cyc["n_consumed_requests"] == len(cyc["consumed_keys"]) == 4
    assert [k[1] for k in cyc["consumed_keys"]] == [1, 2, 3, 4]
    assert cyc["freshness_lag_s"] is not None and cyc["freshness_lag_s"] >= 0


def test_assemble_dedups_cycle_spans_last_wins(tmp_path):
    """A killed cycle is redone after restart and emits its span again —
    exactly-once accounting keeps the LAST (durable) emission."""
    trace.configure(tmp_path)
    _cycle_span(1, version=5, verdict="rollback", reason="auc",
                consumed=[(0, 1, 0, 2)])
    _cycle_span(1, version=6, verdict="promote",
                consumed=[(0, 1, 0, 2)])  # the redo, after restart
    _cycle_span(2, version=7, consumed=[(0, 2, 0, 2)])
    report = assemble(load_spans(tmp_path))
    assert [c["cycle"] for c in report["cycles"]] == [1, 2]
    assert report["cycles"][0]["version"] == 6  # last durable emission wins
    # consumed keys tile the request space exactly once across cycles
    all_keys = [k for c in report["cycles"] for k in c["consumed_keys"]]
    assert len(all_keys) == len(set(all_keys))


def test_assemble_merges_stage_and_heartbeat_spans(tmp_path):
    trace.configure(tmp_path)
    for stage, ms in (("replay", 3.0), ("train", 40.0), ("canary", 9.0)):
        trace.emit("online", "stage", cycle=1, stage=stage, dur_ms=ms)
    _cycle_span(1, version=3, consumed=[(1, 0, 2)])
    for i in range(10):
        trace.emit("fleet", "heartbeat", replica=i % 2, version=3,
                   ms=1.0 + i, canary=(i % 2 == 1), queue_depth=i,
                   batch_fill=0.5)
    report = assemble(load_spans(tmp_path))
    (cyc,) = report["cycles"]
    assert cyc["stages"] == {"replay": 3.0, "train": 40.0, "canary": 9.0}
    fl = report["fleet"]
    assert fl["heartbeats"]["n"] == 10
    assert fl["canary_heartbeats"]["n"] == fl["stable_heartbeats"]["n"] == 5
    assert fl["canary_heartbeats"]["p50_ms"] > fl["stable_heartbeats"]["p50_ms"]
    assert fl["per_replica"][0]["last_queue_depth"] == 8
    assert fl["per_replica"][1]["last_batch_fill"] == 0.5
    # the console report renders every section without raising
    text = format_report(report)
    assert "cycle 1" in text and "replica 0" in text


def test_peeked_batches_emit_no_replay_spans(tmp_path):
    """Shadow-eval reads (peek_batches) are uncommitted and must not count
    toward the exactly-once replay accounting."""
    from tdfo_tpu.data.replay import ReplayConsumer, RequestLog

    log = RequestLog(tmp_path / "rl")
    for i in range(6):
        log.append({"event": "serve_request", "request": f"r{i}", "rows": 2,
                    "outcome": "ok",
                    "features": {"x": [i * 2, i * 2 + 1], "y": [0.5, 0.5],
                                 "label": [1, 1]}})
    log.close()
    trace.configure(tmp_path / "trace")
    c = ReplayConsumer(tmp_path / "rl", schema=SCHEMA, batch_size=4)
    assert len(c.peek_batches(2)) == 2  # held-out gate slice: no spans
    assert load_spans(tmp_path / "trace") == []
    assert c.next_batch() is not None  # a committed read: one span
    (s,) = load_spans(tmp_path / "trace")
    assert s["kind"] == "replay_batch" and s["component"] == "replay"


def test_chrome_trace_shape(tmp_path):
    trace.configure(tmp_path)
    trace.emit("online", "stage", cycle=1, stage="train", dur_ms=40.0)
    trace.emit("frontend", "serve_request", replica=2, seq=9,
               latency_ms=1.5)
    obj = chrome_trace(load_spans(tmp_path))
    events = obj["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    assert {m["args"]["name"] for m in meta} == {"online", "frontend"}
    (complete,) = [e for e in events if e["ph"] == "X"]
    assert complete["name"] == "stage:train" and complete["dur"] == 40e3
    (instant,) = [e for e in events if e["ph"] == "i"]
    assert instant["tid"] == 2 and instant["args"]["seq"] == 9
    json.dumps(obj)  # the whole object must serialize


# ------------------------------------------------- train-loop phases


@pytest.fixture
def fake_clock(monkeypatch):
    """The phases' clock as a list holding the time: tests move it."""
    t = [100.0]
    monkeypatch.setattr(trace, "_now", lambda: t[0])
    trace.reset_epoch_history()
    yield t
    trace.reset_epoch_history()


def test_phase_outside_an_epoch_accumulates_nothing(fake_clock):
    with trace.phase("next_batch"):
        fake_clock[0] += 1.0
        with trace.phase("loader_next"):
            fake_clock[0] += 1.0
    assert trace.epoch_history() == []
    with pytest.raises(ZeroDivisionError):  # the body's error passes through
        with trace.phase("dispatch"):
            1 / 0
    # an epoch that raises before close() records nothing and frees the thread
    with pytest.raises(KeyError):
        with trace.epoch_phases(0):
            with trace.phase("dispatch"):
                raise KeyError("lost batch")
    assert trace.epoch_history() == []
    with trace.epoch_phases(1) as ep:
        ep.close(0)
    assert [r["epoch"] for r in trace.epoch_history()] == [1]


def test_nested_phases_sum_count_max_and_self_time(fake_clock):
    t = fake_clock
    with trace.epoch_phases(3) as ep:
        t[0] += 1.0  # the loop's own time
        for loader_s, put_s in ((2.0, 0.5), (4.0, 0.25)):
            with trace.phase("next_batch"):
                t[0] += 0.125  # next_batch's own
                with trace.phase("loader_next"):
                    t[0] += loader_s
                with trace.phase("h2d_put"):
                    t[0] += put_s
            with trace.phase("dispatch"):
                t[0] += 1.0
        with pytest.raises(RuntimeError, match="already open"):
            trace.epoch_phases(4).__enter__()
        rec = ep.close(steps=2)
        t[0] += 50.0  # after close: on nobody's clock
    assert rec == trace.epoch_history()[-1]
    assert (rec["epoch"], rec["steps"], rec["loop_s"]) == (3, 2, 10.0)
    assert rec["phases"] == {
        "next_batch": [7.0, 2, 4.375], "loader_next": [6.0, 2, 4.0],
        "h2d_put": [0.75, 2, 0.5], "dispatch": [2.0, 2, 1.0]}
    # a parent's self time is its seconds minus its children's
    assert rec["self_s"] == {"next_batch": 0.25, "loader_next": 6.0,
                             "h2d_put": 0.75, "dispatch": 2.0}
    # loop_s minus the outermost phases: the Python loop's own time
    assert rec["loop_self_s"] == 1.0
    json.dumps(rec)


def test_another_threads_phase_stays_out_of_this_epoch(fake_clock):
    import threading

    def worker():
        with trace.phase("loader_next"):
            pass
        with trace.epoch_phases(9) as theirs:  # a thread has an epoch of its own
            with trace.phase("dispatch"):
                pass
            theirs.close(1)

    with trace.epoch_phases(1) as ep:
        with trace.phase("dispatch"):
            th = threading.Thread(target=worker)
            th.start()
            th.join(timeout=30)
            assert not th.is_alive()
            fake_clock[0] += 2.0
        rec = ep.close(1)
    assert rec["phases"] == {"dispatch": [2.0, 1, 2.0]}
    assert [r["epoch"] for r in trace.epoch_history()] == [9, 1]


def _on_a_thread(fn, *args):
    """Run ``fn`` on a thread of its own, to its end."""
    import threading

    errors = []

    def body():
        try:
            fn(*args)
        except BaseException as e:  # handed to the test's thread
            errors.append(e)

    th = threading.Thread(target=body)
    th.start()
    th.join(timeout=30)
    assert not th.is_alive()
    if errors:
        raise errors[0]


def test_a_joined_threads_phases_land_in_the_epochs_record(fake_clock):
    """``prefetch_to_mesh``'s producer: its phases are in the record under
    their names and in nobody's self time but their own."""
    t = fake_clock

    def producer(ep):
        with trace.join_epoch(ep):
            for loader_s, put_s in ((2.0, 0.5), (4.0, 0.25)):
                with trace.phase("loader_next"):
                    t[0] += loader_s
                with trace.phase("h2d_put"):
                    t[0] += put_s
            trace.tally("prefetch_depth", 5)  # a joined thread's tally counts

    with trace.epoch_phases(5) as ep:
        assert trace.current_epoch() is ep
        t[0] += 1.0  # the loop's own time
        with trace.phase("next_batch"):
            t[0] += 0.125
            # the producer works WHILE the loop waits: 6.75 s pass on the
            # clock inside next_batch, none of it next_batch's children
            _on_a_thread(producer, trace.current_epoch())
            trace.tally("prefetch_depth", 1)
            trace.tally("prefetch_empty_takes")
        with trace.phase("h2d_put"):  # a name both threads use adds up
            t[0] += 1.0
        with trace.phase("dispatch"):
            t[0] += 1.0
        rec = ep.close(steps=2)
    assert trace.current_epoch() is None
    assert rec["loop_s"] == 9.875
    assert rec["phases"] == {
        "next_batch": [6.875, 1, 6.875], "loader_next": [6.0, 2, 4.0],
        "h2d_put": [1.75, 3, 1.0], "dispatch": [1.0, 1, 1.0]}
    assert rec["self_s"] == {"next_batch": 6.875, "loader_next": 6.0,
                             "h2d_put": 1.75, "dispatch": 1.0}
    # the loop's own time is the opening thread's alone
    assert rec["loop_self_s"] == 1.0
    assert rec["tallies"] == {"prefetch_depth": [6.0, 2],
                              "prefetch_empty_takes": [1.0, 1]}
    json.dumps(rec)


def test_join_epoch_of_nothing_only_annotates_and_a_late_join_is_dropped(
        fake_clock):
    def unjoined():
        assert trace.current_epoch() is None
        with trace.join_epoch(None):  # eval, a probe of the stream, online
            with trace.phase("loader_next"):
                fake_clock[0] += 1.0
            trace.tally("prefetch_depth", 3)

    def twice(ep):
        with trace.join_epoch(ep):
            # a thread's share of an epoch is not an epoch to hand on
            assert trace.current_epoch() is None
            with pytest.raises(RuntimeError, match="already open"):
                trace.join_epoch(ep).__enter__()

    def late(ep):
        with trace.join_epoch(ep):
            with trace.phase("h2d_put"):
                fake_clock[0] += 1.0

    trace.tally("prefetch_depth", 3)  # no epoch: nothing, and no error
    with trace.epoch_phases(2) as ep:
        _on_a_thread(unjoined)
        _on_a_thread(twice, ep)
        with pytest.raises(RuntimeError, match="already open"):
            trace.join_epoch(ep).__enter__()  # the opening thread cannot join
        rec = ep.close(1)
        _on_a_thread(late, ep)
    assert rec["phases"] == {} and rec["tallies"] == {}
    assert trace.epoch_history() == [rec]
    assert rec["loop_s"] == rec["loop_self_s"] == 1.0


def test_two_threads_phases_at_once_keep_their_own_nesting():
    """Real threads, real clock, a short switch interval: a phase closing
    on one thread while another's is open must not become its child.  With
    one shared open-chain the outer phases' self time would lose the other
    thread's seconds."""
    import sys
    import threading

    n = 3000
    trace.reset_epoch_history()
    go = threading.Event()

    def nest(outer, inner):
        go.wait(30)
        for _ in range(n):
            with trace.phase(outer):
                with trace.phase(inner):
                    pass

    def producer(ep):
        with trace.join_epoch(ep):
            nest("loader_next", "decode")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with trace.epoch_phases(0) as ep:
            threads = [threading.Thread(target=producer, args=(ep,))
                       for _ in range(3)]
            for th in threads:
                th.start()
            go.set()
            nest("next_batch", "take")
            for th in threads:
                th.join(timeout=60)
            assert not any(th.is_alive() for th in threads)
            assert ep._open is None and all(c._open is None for c in ep._joined)
            rec = ep.close(n)
    finally:
        sys.setswitchinterval(interval)
        trace.reset_epoch_history()
    counts = {k: v[1] for k, v in rec["phases"].items()}
    assert counts == {"next_batch": n, "take": n,
                      "loader_next": 3 * n, "decode": 3 * n}
    for outer, inner in (("next_batch", "take"), ("loader_next", "decode")):
        assert rec["self_s"][inner] == rec["phases"][inner][0]
        assert rec["self_s"][outer] == pytest.approx(
            rec["phases"][outer][0] - rec["phases"][inner][0], abs=1e-6)
        assert rec["self_s"][outer] >= 0.0
    top = rec["loop_s"] - rec["loop_self_s"]
    assert top == pytest.approx(rec["phases"]["next_batch"][0], abs=1e-6)


def test_epoch_history_is_bounded_and_survives_configure(fake_clock, tmp_path):
    for e in range(trace.HISTORY_EPOCHS + 6):
        with trace.epoch_phases(e) as ep:
            ep.close(1)
    trace.configure(tmp_path)
    trace.configure(None)
    kept = [r["epoch"] for r in trace.epoch_history()]
    assert kept == list(range(6, trace.HISTORY_EPOCHS + 6))
    trace.reset_epoch_history()
    assert trace.epoch_history() == []


def test_phases_are_spans_of_a_profiler_session(tmp_path):
    """With a session on, every occurrence of a phase is a ``tdfo:<name>``
    event on a host thread's line of the profiler's own trace (the device
    trace's clock) — inside an epoch, where one annotation object serves
    every occurrence of its name, and outside one."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with trace.phase("h2d_put"):
            pass
        with trace.epoch_phases(0) as ep:
            for _ in range(3):
                with trace.phase("next_batch"):
                    with trace.phase("loader_next"):
                        pass
            ep.close(3)
    finally:
        jax.profiler.stop_trace()
        trace.reset_epoch_history()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = [ev.name for plane in
             jax.profiler.ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("tdfo:")]
    assert sorted(names) == (["tdfo:h2d_put"] + ["tdfo:loader_next"] * 3
                             + ["tdfo:next_batch"] * 3)


# ----------------------------------------------- zero-cost jaxpr pin


def _small_sparse_step(mesh8):
    """An unjitted sparse step over two stacked tables, its state and a
    batch."""
    from tdfo_tpu.models.dlrm import DLRMBackbone
    from tdfo_tpu.ops.sparse import sparse_optimizer
    from tdfo_tpu.parallel.embedding import (EmbeddingSpec,
                                             ShardedEmbeddingCollection)
    from tdfo_tpu.train.ctr import ctr_sparse_forward
    from tdfo_tpu.train.sparse_step import (SparseTrainState,
                                            make_sparse_train_step)

    cats = ("c0", "c1")
    sizes = {"c0": 11, "c1": 40}
    specs = [EmbeddingSpec(c, sizes[c], 8, features=(c,), sharding="row")
             for c in cats]
    coll = ShardedEmbeddingCollection(specs, mesh=mesh8, stack_tables=True)
    bb = DLRMBackbone(embed_dim=8, cat_columns=cats, cont_columns=("x0",))
    dummy_e = {c: jnp.zeros((1, 8), jnp.float32) for c in cats}
    dummy_c = {"x0": jnp.zeros((1,), jnp.float32)}
    state = SparseTrainState.create(
        dense_params=bb.init(jax.random.key(1), dummy_e, dummy_c)["params"],
        tx=optax.adam(1e-2),
        tables=coll.init(jax.random.key(0)),
        sparse_opt=sparse_optimizer("rowwise_adagrad", lr=1e-2,
                                    weight_decay=0.0,
                                    small_vocab_threshold=100))
    step = make_sparse_train_step(coll, ctr_sparse_forward(bb),
                                  mode="gspmd", donate=False, jit=False)
    rr = np.random.default_rng(5)
    batch = {c: jnp.asarray(rr.integers(0, sizes[c], 16), jnp.int32)
             for c in cats}
    batch["x0"] = jnp.asarray(rr.random(16, dtype=np.float32))
    batch["label"] = jnp.asarray(rr.integers(0, 2, 16), jnp.float32)
    return step, state, batch


def test_trace_on_step_jaxpr_byte_identical(mesh8, tmp_path):
    """``trace = true`` must add ZERO equations to the train step: spans
    are host-side only, so the step jaxpr with a live trace sink is
    byte-identical to the untraced build (the ``[telemetry] counters``
    laziness pin of test_telemetry.py, applied to tracing)."""
    step, state, batch = _small_sparse_step(mesh8)
    norm = lambda j: re.sub(r"0x[0-9a-f]+", "0xADDR", str(j))
    j_off = norm(jax.make_jaxpr(step)(state, batch))
    trace.configure(tmp_path)
    trace.emit("online", "stage", cycle=1, stage="probe")  # sink is LIVE
    j_on = norm(jax.make_jaxpr(step)(state, batch))
    assert j_on == j_off


def test_sparse_step_sections_carry_named_scopes(mesh8):
    """The step's sections are named in the device program (what a
    profiler trace shows as ``tf_op``), so a reduction from trace to
    metrics finds lookup / dense / update time after a refactor.  Names
    ride the ops' metadata only: the jaxpr pin above is untouched."""
    step, state, batch = _small_sparse_step(mesh8)
    hlo = jax.jit(step).lower(state, batch).as_text(debug_info=True)
    for scope in ("emb_lookup", "dense_fwd_bwd", "dense_update", "emb_update"):
        assert re.search(rf'loc\("[^"]*\b{scope}\b', hlo), scope


# ---------------------------------------------- rotation of sibling sinks


def test_events_log_rotates_at_size(tmp_path):
    from tdfo_tpu.obs import events

    path = tmp_path / "events.jsonl"
    events.configure(path, rotate_bytes=400)
    try:
        for i in range(40):
            events.record("compile", name=f"fn{i}", dur_ms=float(i))
    finally:
        events.configure(None)
    overflow = tmp_path / "events.jsonl.1"
    assert overflow.exists()
    if path.exists():
        assert path.stat().st_size < 400 + 200
    names = []
    for p in (overflow, path):
        if not p.exists():
            continue
        for line in p.read_text().splitlines():
            names.append(json.loads(line)["name"])  # every line complete
    # one generation of history: a contiguous ordered suffix survives
    first = int(names[0][2:])
    assert names == [f"fn{i}" for i in range(first, 40)]


def test_heartbeat_log_rotates_at_size(tmp_path):
    from tdfo_tpu.obs.watchdog import StallWatchdog

    path = tmp_path / "heartbeat.jsonl"
    wd = StallWatchdog(path, 10.0, rotate_bytes=300)
    for i in range(30):
        wd.beat(i)
        wd.check()  # the daemon body writes the heartbeat record
    overflow = tmp_path / "heartbeat.jsonl.1"
    assert overflow.exists()
    if path.exists():
        assert path.stat().st_size < 300 + 300
    steps = []
    for p in (overflow, path):
        if not p.exists():
            continue
        for line in p.read_text().splitlines():
            steps.append(json.loads(line)["last_step"])
    assert steps == sorted(steps)  # one generation retired, order preserved


# ------------------------------------------------------ launch.py obs


def test_launch_obs_subcommand(tmp_path, capsys):
    from tdfo_tpu.launch import main

    out_dir = tmp_path / "run"
    trace.configure(out_dir / "trace")
    trace.emit("frontend", "serve_request", replica=0, seq=1,
               latency_ms=2.0, version=3, digest="d0")
    trace.emit("replay", "replay_batch", rows=4, consumed=[[1, 0, 2]])
    _cycle_span(1, version=3, consumed=[(1, 0, 2)])
    trace.configure(None)
    cfgp = tmp_path / "config.toml"
    cfgp.write_text(f'checkpoint_dir = "{out_dir}"\n')
    assert main(["obs", "--config", str(cfgp)]) == 0
    out = capsys.readouterr().out
    assert "cycle 1" in out and "verdict=promote" in out
    chrome = json.loads((out_dir / "trace" / "chrome_trace.json").read_text())
    assert chrome["traceEvents"]

    (tmp_path / "empty.toml").write_text(
        f'checkpoint_dir = "{tmp_path / "nothing"}"\n')
    with pytest.raises(SystemExit, match="no trace"):
        main(["obs", "--config", str(tmp_path / "empty.toml")])
