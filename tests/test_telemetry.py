"""``[telemetry]`` subsystem: in-graph counters, compile events, stall watchdog.

Three contracts (``tdfo_tpu/obs``, ``core/config.py`` TelemetrySpec):

  * **Counters are free when off and inert when on.**  Emission sites call
    ``counters.emit`` unconditionally but the thunk only runs under an
    active collector, so ``telemetry.counters=false`` traces a jaxpr
    BYTE-identical to a build with no telemetry code at all (pinned below
    by stripping the module), and a counters-on EAGER run is bit-identical
    to counters-off for every optimizer kind and composition (update
    cache, grouped a2a) — eager because two different XLA programs drift
    ~1 ulp from fusion-dependent FMA contraction (the
    ``test_update_cache.py`` convention), while op-by-op execution
    preserves exact equality and counters only ADD ops.
  * **Compile events are counted and retraces are loud.**  Every jax
    compilation lands in ``events.jsonl`` with name/duration/count; the
    serve frontend's bucketed ragged trace compiles exactly one program
    per padded shape; compilations after ``mark_warmup`` warn.
  * **The watchdog notices a wedged loop.**  Heartbeats advance while
    steps complete; a stall past ``stall_timeout_s`` fires ONCE (re-armed
    by recovery) with every thread's Python stack in the record —
    exercised unit-level with an injected clock and end-to-end through
    the ``[faults]`` stall trigger inside a full Trainer fit.
"""

import dataclasses
import json
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tdfo_tpu.models.dlrm import DLRMBackbone
from tdfo_tpu.obs import counters as C
from tdfo_tpu.obs import events
from tdfo_tpu.obs.watchdog import StallWatchdog
from tdfo_tpu.ops.sparse import sparse_optimizer
from tdfo_tpu.parallel.embedding import EmbeddingSpec, ShardedEmbeddingCollection
from tdfo_tpu.train.ctr import ctr_sparse_forward
from tdfo_tpu.train.sparse_step import (
    SparseTrainState,
    make_cache_flush_fn,
    make_sparse_train_step,
)

CATS = ("c0", "c1", "c2")
CONTS = ("x0",)
SIZES = {"c0": 7, "c1": 50, "c2": 300}
N_STEPS = 3


# --------------------------------------------------- unit: the registry


def test_emit_is_lazy_scoped_and_suppressible():
    """No collector -> the value thunk is never evaluated (the zero-cost
    contract); scope() prefixes names; suppress() blacks out a region."""
    calls = []

    def thunk():
        calls.append(1)
        return jnp.float32(3.0)

    assert not C.enabled()
    C.emit("x", thunk)  # falls on the floor, thunk unevaluated
    assert not calls
    with C.collect() as c:
        assert C.enabled()
        C.emit("x", thunk)
        with C.scope("emb/c0/"):
            C.emit("touched", 5)
        with C.suppress():
            assert not C.enabled()
            C.emit("hidden", thunk)
    assert not C.enabled()
    got = {k: float(v) for k, v in c.items()}
    assert got == {"x": 3.0, "emb/c0/touched": 5.0}
    assert len(calls) == 1  # the suppressed emit never ran its thunk


def test_nested_collectors_are_independent():
    with C.collect() as outer:
        C.emit("a", 1)
        with C.collect() as inner:
            C.emit("b", 2)
        C.emit("c", 3)
    assert set(outer) == {"a", "c"} and set(inner) == {"b"}


# ------------------------------------- trajectory bit-equivalence (eager)


def _build(mesh, kind, *, cache_rows=0, grouped=False, flush_counters=False):
    """The test_update_cache.py harness, jit=False throughout: counters
    can only be read across an eager step (a collector cannot see through
    an inner jit boundary), and eager execution is what makes the
    on-vs-off comparison exactly bitwise."""
    specs = [EmbeddingSpec(c, SIZES[c], 8, features=(c,), sharding="row")
             for c in CATS]
    coll = ShardedEmbeddingCollection(
        specs, mesh=mesh, stack_tables=not grouped, grouped_a2a=grouped,
        cache_rows=cache_rows)
    bb = DLRMBackbone(embed_dim=8, cat_columns=CATS, cont_columns=CONTS)
    dummy_e = {c: jnp.zeros((1, 8), jnp.float32) for c in CATS}
    dummy_c = {c: jnp.zeros((1,), jnp.float32) for c in CONTS}
    state = SparseTrainState.create(
        dense_params=bb.init(jax.random.key(1), dummy_e, dummy_c)["params"],
        tx=optax.adam(1e-2),
        tables=coll.init(jax.random.key(0)),
        sparse_opt=sparse_optimizer(kind, lr=1e-2, weight_decay=1e-3,
                                    small_vocab_threshold=100))
    flush = None
    if cache_rows:
        caches = coll.init_caches(state.tables, state.sparse_opt)
        state = dataclasses.replace(state, slots={**state.slots, **caches})
        flush = make_cache_flush_fn(donate=False, jit=False,
                                    counters=flush_counters)
    step = make_sparse_train_step(
        coll, ctr_sparse_forward(bb), mode="alltoall" if grouped else "gspmd",
        donate=False, jit=False)
    return step, flush, state


def _batches(n):
    rr = np.random.default_rng(12)
    for _ in range(n):
        batch = {c: jnp.asarray(rr.integers(0, SIZES[c], 32), jnp.int32)
                 for c in CATS}
        batch["x0"] = jnp.asarray(rr.random(32, dtype=np.float32))
        batch["label"] = jnp.asarray(rr.integers(0, 2, 32), jnp.float32)
        yield batch


def _traj(mesh, kind, *, cache_rows=0, grouped=False, counters=False,
          n=N_STEPS):
    step, flush, state = _build(mesh, kind, cache_rows=cache_rows,
                                grouped=grouped, flush_counters=counters)
    losses, ctr_log = [], []
    for i, batch in enumerate(_batches(n)):
        if counters:
            with C.collect() as c:
                state, loss = step(state, batch)
            ctr_log.append({k: float(v) for k, v in c.items()})
        else:
            state, loss = step(state, batch)
        losses.append(
            np.asarray(loss).astype(np.float32).view(np.uint32).item())
        if flush is not None and (i + 1) % 2 == 0:
            if counters:
                state, over, fc = flush(state)
                ctr_log[-1].update({k: float(v) for k, v in fc.items()})
            else:
                state, over = flush(state)
            assert all(int(v) == 0 for v in over.values()), over
    if flush is not None:
        out = flush(state)
        state, over = out[0], out[1]
        assert all(int(v) == 0 for v in over.values()), over
    return losses, state, ctr_log


def _assert_state_bitwise(s0, s1, ctx=""):
    for a in s0.tables:
        np.testing.assert_array_equal(
            np.asarray(s0.tables[a]).view(np.uint32),
            np.asarray(s1.tables[a]).view(np.uint32),
            err_msg=f"{ctx}: table {a}")
    for a in s0.slots:
        for j, (x, y) in enumerate(zip(
                jax.tree_util.tree_leaves(s0.slots[a]),
                jax.tree_util.tree_leaves(s1.slots[a]))):
            assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), \
                f"{ctx}: slot {a} leaf {j}"
    for j, (x, y) in enumerate(zip(
            jax.tree_util.tree_leaves(s0.dense_params),
            jax.tree_util.tree_leaves(s1.dense_params))):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes(), \
            f"{ctx}: dense leaf {j}"


@pytest.mark.parametrize("kind", [
    # tier-1 keeps the north-star rowwise kind; the other slot layouts
    # cover the same emit sites (test_update_cache slow-marking idiom)
    pytest.param("sgd", marks=pytest.mark.slow),
    pytest.param("adagrad", marks=pytest.mark.slow),
    "rowwise_adagrad",
    pytest.param("adam", marks=pytest.mark.slow),
])
def test_counters_do_not_change_trajectory(mesh8, kind):
    """Counters-on vs counters-off, same seed, eager: losses and final
    state bit-identical for every optimizer kind — and the collector
    actually filled (per-table touched counts + grad/param norms)."""
    l_off, s_off, _ = _traj(mesh8, kind)
    l_on, s_on, ctrs = _traj(mesh8, kind, counters=True)
    assert l_off == l_on
    _assert_state_bitwise(s_off, s_on, kind)
    assert len(ctrs) == N_STEPS
    for c in ctrs:
        assert "grad_norm" in c and "param_norm" in c
        touched = {k: v for k, v in c.items()
                   if k.startswith("emb/") and k.endswith("touched_ids")}
        assert touched, sorted(c)
        # every id in the synthetic batch is valid (no negative padding)
        assert sum(touched.values()) == 32 * len(CATS)
        assert c["grad_norm"] > 0 and c["param_norm"] > 0


@pytest.mark.slow  # 2 eager trajectories; tier-1 covers the cache counters
# + hit_rate end-to-end via test_trainer_full_telemetry_run
def test_counters_cache_composition(mesh8):
    """Update-cache run: hit/miss counters ride the step, flushed/resident
    ride the flush program — and the trajectory stays bit-identical."""
    kw = dict(cache_rows=1024)
    l_off, s_off, _ = _traj(mesh8, "rowwise_adagrad", **kw)
    l_on, s_on, ctrs = _traj(mesh8, "rowwise_adagrad", counters=True, **kw)
    assert l_off == l_on
    _assert_state_bitwise(s_off, s_on, "cache")
    seen = set().union(*ctrs)
    for suffix in ("cache_hit_rows", "cache_miss_rows"):
        assert any(k.startswith("emb/") and k.endswith(suffix)
                   for k in seen), (suffix, sorted(seen))
    # flush-step records carry the write-back counters
    flush_recs = [c for c in ctrs
                  if any(k.endswith("cache_flushed_rows") for k in c)]
    assert flush_recs
    # step 0 is all misses (cold cache); flushed rows cover what was dirty
    first = ctrs[0]
    hits0 = sum(v for k, v in first.items() if k.endswith("cache_hit_rows"))
    misses0 = sum(v for k, v in first.items() if k.endswith("cache_miss_rows"))
    assert hits0 == 0 and misses0 > 0


@pytest.mark.slow  # 2 eager trajectories; the shard_map suppression
# mechanism stays tier-1-covered by test_trainer_a2a_fill_telemetry
def test_counters_grouped_a2a_composition(mesh8):
    """Grouped cross-table exchange (shard_map inside): emission inside
    manual-SPMD bodies is suppressed rather than leaking tracers, the
    step-level norms still report, and the math is untouched."""
    l_off, s_off, _ = _traj(mesh8, "sgd", grouped=True)
    l_on, s_on, ctrs = _traj(mesh8, "sgd", grouped=True, counters=True)
    assert l_off == l_on
    _assert_state_bitwise(s_off, s_on, "grouped")
    for c in ctrs:
        assert "grad_norm" in c and "param_norm" in c


def test_counters_off_jaxpr_byte_identical(mesh8, monkeypatch):
    """The laziness pin: tracing with no collector produces the SAME jaxpr
    text as tracing with emit/enabled stubbed out entirely — counters=false
    cannot cost even one equation.  (Addresses normalised: jaxpr printing
    embeds object ids.)"""
    step, _, state = _build(mesh8, "rowwise_adagrad")
    batch = next(_batches(1))
    norm = lambda j: re.sub(r"0x[0-9a-f]+", "0xADDR", str(j))

    def step_with_ctrs(state, batch):
        # how the trainer wires counters: they ride the return pytree
        with C.collect() as c:
            state, loss = step(state, batch)
        return state, loss, dict(c)

    j_on = norm(jax.make_jaxpr(step_with_ctrs)(state, batch))
    j_off = norm(jax.make_jaxpr(step)(state, batch))
    monkeypatch.setattr(C, "enabled", lambda: False)
    monkeypatch.setattr(C, "emit", lambda *a, **k: None)
    j_stripped = norm(jax.make_jaxpr(step)(state, batch))
    assert j_off == j_stripped
    assert j_on != j_off  # the pin detects what it claims to detect


# ------------------------------------------------------- stall watchdog


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


def test_watchdog_detects_stall_and_rearms(tmp_path):
    hb = tmp_path / "heartbeat.jsonl"
    clk = FakeClock()
    wd = StallWatchdog(hb, 10.0, clock=clk)
    wd.beat(1)
    clk.advance(5.0)
    assert wd.check() is False  # fresh heartbeat, no stall
    clk.advance(6.0)  # age 11 s > 10 s
    assert wd.check() is True  # fires exactly once...
    assert wd.check() is False  # ...until a beat re-arms it
    wd.beat(2)
    assert wd.check() is False  # recovered
    clk.advance(11.0)
    assert wd.check() is True  # re-armed detection fires again
    lines = [json.loads(l) for l in hb.read_text().splitlines()]
    stalls = [l for l in lines if l.get("kind") == "stall"]
    assert len(stalls) == 2 == len(wd.stall_events)
    # the dump names this very function's frame — diagnosable from the log
    assert "test_watchdog_detects_stall" in stalls[0]["stacks"]
    assert stalls[0]["last_step"] == 1 and stalls[1]["last_step"] == 2
    beats = [l for l in lines if "stalled" in l]
    steps = [l["last_step"] for l in beats]
    assert steps == sorted(steps) and steps[-1] == 2  # monotone heartbeat


def test_watchdog_thread_lifecycle(tmp_path):
    wd = StallWatchdog(tmp_path / "hb.jsonl", 0.08)
    wd.start()
    assert wd._thread is not None and wd._thread.daemon
    import time as _time

    # no beat -> the daemon's poll fires a stall; under loaded xdist workers
    # that takes longer than a few poll intervals, so wait for it
    deadline = _time.monotonic() + 10.0
    while not wd.stall_events and _time.monotonic() < deadline:
        _time.sleep(0.02)
    wd.stop()
    assert wd._thread is None
    assert wd.stall_events  # the daemon itself detected the silence
    # zero timeout = disabled: start() must not spawn a thread
    off = StallWatchdog(tmp_path / "hb2.jsonl", 0.0)
    off.start()
    assert off._thread is None
    off.stop()


# ------------------------------------------- compile events + retraces


def test_compile_events_count_frontend_programs(tmp_path, caplog):
    """The frontend's bucketed ragged trace compiles EXACTLY one program
    per padded shape (the bounded-jit-cache contract, now observable), a
    steady-state replay adds zero, and a post-warmup compile warns."""
    from tdfo_tpu.serve.frontend import MicroBatcher

    path = tmp_path / "events.jsonl"
    events.configure(path)
    try:
        assert events.active()

        def bucketed_score(batch):
            return batch["x"] * 2.0

        score = jax.jit(bucketed_score)

        def trace(mb):
            rng = np.random.default_rng(0)
            for i in range(24):
                n = int(rng.integers(1, 33))
                mb.submit(f"r{i}", {"x": np.arange(n, dtype=np.float32)})
                mb.poll()
            mb.drain()

        mb = MicroBatcher(score, buckets=(8, 16, 32), max_batch=32,
                          batch_deadline_ms=0.0)
        trace(mb)
        shapes = {padded for _, padded in mb.shipped}
        assert shapes
        n_compiles = events.compile_count("bucketed_score")
        assert n_compiles == len(shapes) <= 3
        events.mark_warmup()
        # steady state: same buckets hit the jit cache, zero new programs
        mb2 = MicroBatcher(score, buckets=(8, 16, 32), max_batch=32,
                           batch_deadline_ms=0.0)
        trace(mb2)
        assert events.compile_count("bucketed_score") == n_compiles
        # a genuinely new program after warmup is flagged LOUDLY
        with caplog.at_level(logging.WARNING, logger="tdfo_tpu.obs.events"):
            jax.jit(lambda x: x - 1.0)(jnp.zeros((3,), jnp.float32))
        assert any("UNEXPECTED RETRACE" in r.getMessage()
                   for r in caplog.records)
        recs = [json.loads(l) for l in path.read_text().splitlines()]
        compiles = [r for r in recs if r["kind"] == "compile"]
        assert any("bucketed_score" in r["name"] for r in compiles)
        assert all(r["duration_s"] >= 0 and r["count"] >= 1
                   for r in compiles)
        assert any(r["kind"] == "warmup_done" for r in recs)
        late = [r for r in compiles if r["after_warmup"]]
        assert late  # the post-warmup lambda landed with the flag set
    finally:
        events.configure(None)
    assert not events.active()
    assert events.compile_count() == 0  # detached recorder counts nothing


def test_events_do_not_leak_debug_spam_to_console(tmp_path):
    """jax mounts a level-NOTSET stderr StreamHandler on the "jax" logger,
    so lowering the dispatch logger to DEBUG would flood the console via
    propagation.  While recording, the DEBUG records must stay out of the
    parent chain; records at the logger's ORIGINAL threshold (real
    warnings) still pass through, and propagation is restored on stop."""
    jl = logging.getLogger("jax._src.dispatch")
    sink = logging.Handler(level=logging.DEBUG)
    seen = []
    sink.emit = seen.append
    root = logging.getLogger()
    root.addHandler(sink)
    try:
        events.configure(tmp_path / "ev.jsonl")
        jax.jit(lambda x: x * 3.0)(jnp.ones((4,), jnp.float32))
        assert events.compile_count() >= 1  # the recorder saw the compiles
        leaked = [r for r in seen if r.name == "jax._src.dispatch"
                  and r.levelno < logging.WARNING]
        assert not leaked, [r.getMessage() for r in leaked]
        jl.warning("dispatch warning passthrough")
        assert any(r.getMessage() == "dispatch warning passthrough"
                   for r in seen)
    finally:
        events.configure(None)
        root.removeHandler(sink)
    assert jl.propagate  # restored


def test_memory_snapshot_gated_on_backend():
    """Spoofed CPU devices expose no memory_stats: the sampler returns
    None instead of fabricating numbers, and the peak watermark is empty."""
    assert events.memory_snapshot() is None
    assert events.peak_memory() == {}


# ------------------------------------------------- config + MetricLogger


def test_telemetry_config_validation():
    from tdfo_tpu.core.config import read_configs

    cfg = read_configs(None, model="dlrm",
                       telemetry={"counters": True, "events": True,
                                  "stall_timeout_s": 5.0})
    assert cfg.telemetry.counters and cfg.telemetry.events
    assert cfg.telemetry.stall_timeout_s == 5.0
    dflt = read_configs(None, model="dlrm")
    assert not dflt.telemetry.counters and not dflt.telemetry.events
    assert dflt.telemetry.stall_timeout_s == 0.0
    with pytest.raises(ValueError, match="telemetry"):
        read_configs(None, model="dlrm", telemetry={"bogus": 1})
    with pytest.raises(ValueError, match="stall_timeout_s"):
        read_configs(None, model="dlrm", telemetry={"stall_timeout_s": -1.0})


def test_events_and_watchdog_need_an_output_dir():
    """events.jsonl / heartbeat.jsonl have nowhere to go without a
    checkpoint_dir or log_dir — refuse at construction, not mid-fit."""
    from tdfo_tpu.core.config import read_configs
    from tdfo_tpu.train.trainer import Trainer

    with pytest.raises(ValueError, match="checkpoint_dir"):
        Trainer(read_configs(None, model="twotower",
                             telemetry={"stall_timeout_s": 1.0}))
    with pytest.raises(ValueError, match="checkpoint_dir"):
        Trainer(read_configs(None, model="twotower",
                             telemetry={"events": True}))


def test_metric_logger_coerces_numpy_scalars(tmp_path, capsys):
    """Fetched device values arrive as numpy scalars/0-d arrays — the
    logger must coerce them to native types (json.dumps rejects np.float32)
    and route them through the float-format branch."""
    from tdfo_tpu.train.trainer import MetricLogger

    lg = MetricLogger(tmp_path)
    lg.log(step=np.int64(3), loss=np.float32(0.25),
           fill=np.float64(0.5) + np.zeros(()), plain=7)
    lg.close()
    rec = json.loads((tmp_path / "metrics.jsonl").read_text().splitlines()[0])
    assert rec["step"] == 3 and type(rec["step"]) is int
    assert rec["loss"] == 0.25 and type(rec["loss"]) is float
    assert rec["fill"] == 0.5 and rec["plain"] == 7
    out = capsys.readouterr().out
    assert "loss=0.25000" in out  # float formatting applied post-coercion


# ------------------------------------------- end-to-end: a full fit


@pytest.fixture(scope="module")
def tele_data(tmp_path_factory):
    from tdfo_tpu.data.ctr_preprocessing import run_ctr_preprocessing
    from tdfo_tpu.data.synthetic import write_synthetic_goodreads

    d = tmp_path_factory.mktemp("gr_tele")
    write_synthetic_goodreads(d, n_users=64, n_books=100,
                              interactions_per_user=(12, 30), seed=3)
    ctr = run_ctr_preprocessing(d)
    return d, ctr


def _tele_cfg(d, ctr, **kw):
    from tdfo_tpu.core.config import read_configs

    return read_configs(
        None, data_dir=d, model="twotower", model_parallel=True,
        mesh={"data": 4, "model": 2}, n_epochs=1, learning_rate=3e-3,
        embed_dim=8, per_device_train_batch_size=16,
        per_device_eval_batch_size=16, shuffle_buffer_size=500,
        log_every_n_steps=2, size_map=ctr,
        sparse_optimizer="rowwise_adagrad", **kw)


def test_trainer_full_telemetry_run(tele_data, tmp_path, capsys):
    """The acceptance run: counters + events + watchdog + update cache +
    an injected [faults] stall, one 8-device fit.  metrics.jsonl carries
    per-table touched counts, cache hit rate and grad/param norms at the
    log cadence; events.jsonl records the compilations and the final
    run summary; heartbeat.jsonl advances monotonically and the injected
    stall trips the watchdog end-to-end."""
    from tdfo_tpu.train.trainer import Trainer

    d, ctr = tele_data
    cfg = _tele_cfg(
        d, ctr,
        embeddings={"cache_rows": 512, "flush_every": 3},
        faults={"stall_at_step": 2, "stall_seconds": 1.0},
        telemetry={"counters": True, "events": True, "stall_timeout_s": 0.25})
    tr = Trainer(cfg, log_dir=tmp_path)
    metrics = tr.fit()
    assert np.isfinite(metrics["eval_loss"])

    recs = [json.loads(l)
            for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    step_recs = [r for r in recs if "grad_norm" in r]
    assert step_recs  # counters landed at the existing log cadence
    last = step_recs[-1]
    assert last["param_norm"] > 0
    assert any(k.startswith("emb/") and k.endswith("touched_ids")
               for k in last), sorted(last)
    rate_keys = [k for r in step_recs for k in r
                 if k.endswith("cache_hit_rate")]
    assert rate_keys  # the cache composition reports hit rate
    assert all(0.0 <= r[k] <= 1.0
               for r in step_recs for k in r if k.endswith("cache_hit_rate"))

    ev = [json.loads(l)
          for l in (tmp_path / "events.jsonl").read_text().splitlines()]
    assert any(e["kind"] == "compile" for e in ev)
    assert any(e["kind"] == "warmup_done" for e in ev)
    assert ev[-1]["kind"] == "run_summary"  # fit() detached the recorder
    assert not events.active()

    hb = [json.loads(l)
          for l in (tmp_path / "heartbeat.jsonl").read_text().splitlines()]
    steps = [l["last_step"] for l in hb if "stalled" in l]
    assert steps and steps == sorted(steps)  # heartbeat advanced, monotone
    assert steps[-1] >= 2
    # the injected 1.0 s stall (timeout 0.25 s) tripped the watchdog
    assert "[faults] injected 1.0s stall" in capsys.readouterr().out
    assert tr._watchdog is not None and tr._watchdog.stall_events
    assert any(l.get("kind") == "stall" and "stacks" in l for l in hb)


def test_trainer_a2a_fill_telemetry(tele_data, tmp_path):
    """alltoall regime: the log-cadence fill probe reports exchange-bucket
    utilisation in (0, 1] and zero dropped ids at the default (exact)
    capacity."""
    from tdfo_tpu.train.trainer import Trainer

    d, ctr = tele_data
    cfg = _tele_cfg(d, ctr, lookup_mode="alltoall",
                    telemetry={"counters": True})
    tr = Trainer(cfg, log_dir=tmp_path)
    tr.fit()
    recs = [json.loads(l)
            for l in (tmp_path / "metrics.jsonl").read_text().splitlines()]
    fills = [r for r in recs if "a2a_fill" in r]
    assert fills
    assert all(0.0 < r["a2a_fill"] <= 1.0 for r in fills)
    assert all(r["a2a_dropped_ids"] == 0 for r in fills)
    assert all("grad_norm" in r for r in fills)
