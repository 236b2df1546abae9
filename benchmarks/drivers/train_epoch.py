"""Driver for training cells: the window is whole ``Trainer.train_epoch`` calls.

This is the ONE module of the benchmark that touches ``tdfo_tpu``.  What it
takes from the program, by name (a later PR that renames one of these changes
what a user of ``launch train`` sees too, and needs a ``benchmark`` PR first):

  ``core.config.read_configs``   the program's own config reader
  ``train.trainer.Trainer``      ``Trainer(cfg, devices=...)``, ``.train_epoch(e)``
                                 (returns the epoch's mean loss), ``.train_step``
                                 (looked up per call), ``.state``, ``.coll``,
                                 ``._logged_steps`` (batches its loop has fed),
                                 ``._train_batches(e)`` (loader probe only)
  ``SparseTrainState``           ``.step``, ``.tables``, ``.slots``, ``.dense_params``,
                                 ``.opt_state`` (optax: first element with ``.mu``)
  ``coll.features()/resolve()/fat_layout_for()``   where a column's rows live
  ``ops.pallas_kernels.fat_pack/fat_unpack``       fused-line storage in and out

One run: set-up (data from the seed -> ``Trainer`` -> the benchmark's own
weights from the seed -> ONE warm-up ``train_epoch`` whose first steps are
recorded) -> window (``train_epoch`` until ``seconds`` have passed; its steps
counted from the program's own counters) -> memory peak -> state freed ->
plain reference over the recorded steps -> compare.
Sizes are arguments, so ``benchmarks/tests`` drives this same code tiny on CPU
devices; only ``run.py``'s ``main`` decides what device is acceptable."""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import shutil
import sys
import tempfile
from pathlib import Path

import numpy as np

from benchmarks.lib import compare as cmp
from benchmarks.lib import monitor, traffic as traffic_lib, weights

RECORDED_STEPS = 3


# --------------------------------------------------------------- the program


def build_config(config: dict, *, data_dir: Path, out_dir: Path, seed: int,
                 on_tpu: bool):
    """The program's own ``read_configs`` on the configuration file's
    ``program`` table: every key of the deployment is stated under
    ``benchmarks/``, none is read from a file a later PR may change."""
    from tdfo_tpu.core.config import read_configs

    raw = {k: dict(v) if isinstance(v, dict) else v
           for k, v in config["program"].items()}
    raw.update(data_dir=str(data_dir), checkpoint_dir=str(out_dir),
               seed=int(seed) & 0x7FFFFFFF, use_tpu=on_tpu)
    return read_configs(None, **raw)


def build_trainer(cfg, devices):
    from tdfo_tpu.train.trainer import Trainer

    return Trainer(cfg, devices=devices)


def _paths(tree, prefix="") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_paths(v, f"{prefix}{k}/"))
        else:
            out[f"{prefix}{k}"] = v
    return out


def _unpaths(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *parents, leaf = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[leaf] = v
    return out


class Placement:
    """Where each categorical column's rows live in the program's state."""

    def __init__(self, trainer, columns: dict[str, int], seed: int):
        coll = trainer.coll
        self.coll = coll
        self.kind = trainer.state.sparse_opt.kind
        self.columns = {}
        served = set(coll.features())
        for col, vocab in columns.items():
            if col not in served:
                raise ValueError(f"the program serves no table for column "
                                 f"{col!r} (it serves {sorted(served)})")
            aname, spec, offset = coll.resolve(col)
            if spec.num_embeddings != vocab:
                raise ValueError(f"{col}: the program built {spec.num_embeddings} "
                                 f"rows, the configuration states {vocab}")
            self.columns[col] = dict(
                table_info({col: vocab}, spec.embedding_dim, seed)[col],
                array=aname, offset=int(offset))
        extra = set(trainer.state.tables) - {c["array"] for c in self.columns.values()}
        if extra:
            raise ValueError(f"state arrays the benchmark has no weights for: "
                             f"{sorted(extra)} (hot/cold, int8 sidecars and "
                             "caches need a driver of their own)")

    def layout(self, aname):
        return self.coll.fat_layout_for(aname)


def install_weights(trainer, place: Placement, dense: dict[str, np.ndarray]):
    """Replace the program's initial tables and dense parameters by the
    benchmark's, array for array in the program's own storage and placement.
    Each table array is made on its devices in one jitted call."""
    import jax
    import jax.numpy as jnp
    from tdfo_tpu.ops.pallas_kernels import fat_pack

    state = trainer.state
    tables = dict(state.tables)
    by_array: dict[str, list[dict]] = {}
    for c in place.columns.values():
        by_array.setdefault(c["array"], []).append(c)
    for aname, members in by_array.items():
        old = tables[aname]
        fat = old.ndim == 3
        layout = place.layout(aname) if fat else None
        total = old.shape[0] * (layout.r if fat else 1)
        dim = members[0]["dim"]
        members = sorted(members, key=lambda c: c["offset"])

        def make(members=members, total=total, dim=dim, fat=fat, layout=layout,
                 dtype=old.dtype):
            parts, at = [], 0
            for c in members:
                if c["offset"] > at:
                    parts.append(jnp.zeros((c["offset"] - at, dim), jnp.float32))
                parts.append(weights.embedding_rows(
                    jnp, c["key"], jnp.arange(c["vocab"]), dim, c["scale"]))
                at = c["offset"] + c["vocab"]
            if total > at:
                parts.append(jnp.zeros((total - at, dim), jnp.float32))
            t = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
            if fat:
                return fat_pack(t, kind=layout.kind, layout=layout, dtype=dtype)
            return t.astype(dtype)

        new = jax.jit(make, out_shardings=old.sharding)()
        if new.shape != old.shape or new.dtype != old.dtype:
            raise ValueError(f"{aname}: made {new.shape} {new.dtype}, the "
                             f"program holds {old.shape} {old.dtype}")
        tables[aname] = new
        del old
    flat = _paths(state.dense_params)
    if set(flat) != set(dense):
        raise ValueError(f"dense leaves differ: program {sorted(flat)}, "
                         f"benchmark {sorted(dense)}")
    new_dense = _unpaths({k: jax.device_put(jnp.asarray(dense[k]), v.sharding)
                          for k, v in flat.items()})
    trainer.state = dataclasses.replace(state, tables=tables,
                                        dense_params=new_dense)
    del state, tables
    gc.collect()


def make_reader(place: Placement):
    """One jitted program: for ids ``{column: [n]}``, the rows and the
    optimizer slots the program holds for them."""
    import jax
    import jax.numpy as jnp
    from tdfo_tpu.ops.pallas_kernels import fat_unpack

    def read(tables, slots, ids):
        out = {}
        for col, c in place.columns.items():
            arr, sl = tables[c["array"]], slots[c["array"]]
            gid = ids[col].astype(jnp.int32) + c["offset"]
            if arr.ndim == 3:
                lay = place.layout(c["array"])
                lines = jnp.take(arr, gid // lay.r, axis=0)
                parts = fat_unpack(lines, lay)  # each [n * r, ...]
                pick = lambda a: jnp.take_along_axis(
                    a.reshape(gid.shape[0], lay.r, *a.shape[1:]),
                    (gid % lay.r).reshape(-1, 1, *([1] * (a.ndim - 1))),
                    axis=1)[:, 0]
                rows, *state = [pick(a) for a in parts]
            else:
                rows = jnp.take(arr, gid, axis=0)
                state = [jnp.take(s, gid, axis=0) for s in sl if s.ndim >= 1]
            out[col] = (rows.astype(jnp.float32),
                        tuple(s.astype(jnp.float32) for s in state))
        return out

    return jax.jit(read)


class Recorder:
    """Stands where ``trainer.train_step`` stands for the warm-up epoch and
    records what the first steps were fed and what they left: the window's
    own call and feed, not a second program."""

    def __init__(self, trainer, reader, place: Placement, steps=RECORDED_STEPS):
        self.inner = trainer.train_step
        self.reader, self.place, self.steps = reader, place, steps
        self.calls = 0
        self.batches, self.losses = [], []
        self.after_first = None   # {column: (rows, slots)} at batch 1's ids
        self.dense_m1 = None
        self.after_last = []      # per batch, at that batch's ids
        self.dense_last = None

    def __call__(self, state, batch, *rest):
        out = self.inner(state, batch, *rest)
        self.calls += 1
        if self.calls <= self.steps:
            new = out[0]
            self.batches.append(batch)
            self.losses.append(out[1])
            ids = lambda b: {c: b[c] for c in self.place.columns}
            if self.calls == 1:
                self.after_first = self.reader(new.tables, new.slots, ids(batch))
                self.dense_m1 = next(s.mu for s in new.opt_state
                                     if hasattr(s, "mu"))
            if self.calls == self.steps:
                self.after_last = [self.reader(new.tables, new.slots, ids(b))
                                   for b in self.batches]
                self.dense_last = new.dense_params
        return out

    def fetch(self) -> dict:
        """Everything recorded, on the host."""
        import jax

        return jax.device_get(dict(
            batches=self.batches, losses=self.losses,
            after_first=self.after_first, dense_m1=_paths(self.dense_m1),
            after_last=self.after_last, dense_last=_paths(self.dense_last)))


def annotate_stream(batches):
    """The train stream with each ``next()`` under a span of the profiler's
    own clock: time the host loop spends waiting for (and shipping) a batch."""
    import jax

    it = iter(batches)
    while True:
        with jax.profiler.TraceAnnotation("bench:next_batch"):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


class StepTracer:
    """Stands where ``trainer.train_step`` stands for ONE epoch of a traced
    run: starts the profiler at call ``first`` and stops it ``steps`` calls
    later, once that step's loss is ready."""

    def __init__(self, inner, trace_dir: Path, first: int, steps: int):
        self.inner, self.dir = inner, trace_dir
        self.first, self.last = first, first + steps
        self.calls = 0
        self.done = False

    def __call__(self, *args):
        import jax

        self.calls += 1
        if self.calls == self.first and not self.done:
            # the Python tracer slows a host-bound loop by a large factor and
            # would inflate the idle share; host spans come from the
            # profiler's own TraceMe lines and the two annotations below
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(self.dir), profiler_options=options)
        with jax.profiler.TraceAnnotation("bench:train_step_call"):
            out = self.inner(*args)
        if self.calls == self.last and not self.done:
            jax.block_until_ready(out[1])
            jax.profiler.stop_trace()
            self.done = True
        return out

    def close(self) -> None:
        import jax

        if self.calls >= self.first and not self.done:
            jax.profiler.stop_trace()
            self.done = True


@contextlib.contextmanager
def traced_epoch(trainer, trace_dir: Path, traffic: dict):
    """For one ``train_epoch`` call: the profiler round a steady stretch of
    its steps, and the two host spans.  Everything is put back on the way
    out."""
    tracer = StepTracer(trainer.train_step, trace_dir,
                        first=int(traffic.get("trace_first_step", 8)),
                        steps=int(traffic.get("trace_steps", 32)))
    stream = trainer._train_batches
    trainer.train_step = tracer
    trainer._train_batches = lambda *a, **k: annotate_stream(stream(*a, **k))
    try:
        yield
    finally:
        tracer.close()
        trainer.train_step = tracer.inner
        del trainer._train_batches  # back to the class's method


# ------------------------------------------------------------------ the check


def _first_by_id(ids: np.ndarray, uids: np.ndarray) -> np.ndarray:
    """For each id of ``uids`` (sorted unique), an index into ``ids`` where it
    occurs."""
    order = np.argsort(ids, kind="stable")
    return order[np.searchsorted(ids[order], uids)]


def table_info(columns: dict[str, int], dim: int, seed: int) -> dict:
    """What the reference needs to know of each table: its seed key, its
    stated init scale, its size."""
    return {c: dict(vocab=int(v), dim=int(dim), key=weights.table_key(seed, c),
                    scale=weights.embedding_scale(int(v), int(dim)))
            for c, v in columns.items()}


def reference_side(config: dict, tinfo: dict, continuous: list, feed: list,
                   seed: int, dense_shapes: dict, *, compute="float32",
                   fault=None):
    """The plain reference over ``feed`` (host batches) from the benchmark's
    own weights.  Returns its readings and the rows they are over."""
    import jax

    ref = importlib.import_module(
        f"benchmarks.reference.{config['reference']['module']}")
    cats = list(tinfo)
    model = {**config["reference"]["model"], "categorical": cats,
             "continuous": list(continuous)}
    optim = config["reference"]["optimizer"]
    dim = next(iter(tinfo.values()))["dim"]
    all_ids = {c: np.concatenate([b[c] for b in feed]) for c in cats}
    uids = {c: np.unique(all_ids[c]) for c in cats}
    # compact tables padded to one row per lookup, so that the reference's
    # shapes (and its compiled step) do not depend on the seed; the padding
    # rows are never looked up
    pad = {c: np.concatenate([uids[c], np.full(
        len(all_ids[c]) - len(uids[c]), np.iinfo(np.int32).max, np.int64)])
        for c in cats}
    tables0 = {c: (pad[c], weights.embedding_rows(
        np, tinfo[c]["key"], pad[c] % tinfo[c]["vocab"], dim,
        tinfo[c]["scale"])) for c in cats}
    dense0 = weights.dense_params(seed, dense_shapes)
    r = jax.device_get(ref.run_steps(model, optim, tables0, dense0, feed,
                                     compute=compute, fault=fault))
    rows0 = {c: tables0[c][1][:len(uids[c])] for c in cats}
    side = dict(kind=optim["sparse"]["kind"],
                sparse_b1=optim["sparse"].get("b1", 0.9),
                dense_b1=optim["dense"]["b1"], dim=dim, rows0=rows0,
                dense0=dense0)
    readings = cmp.readings(
        **side, losses=r["losses"], slots1=r["slots1"],
        rows={c: r["rows"][c][:len(uids[c])] for c in cats},
        dense_m1=r["dense_m1"], dense=r["dense"])
    return readings, side, all_ids, uids


def check(config: dict, tinfo: dict, continuous: list, rec: dict, seed: int,
          written_keys: np.ndarray):
    """The program's readings (``rec`` is ``Recorder.fetch()``), the plain
    reference's over the same rows, and the comparison."""
    cats = list(tinfo)
    feed = [{k: np.asarray(v) for k, v in b.items()} for b in rec["batches"]]
    reference, side, all_ids, uids = reference_side(
        config, tinfo, continuous, feed, seed,
        {k: v.shape for k, v in rec["dense_last"].items()})

    # the program's side, reduced to the same rows
    p_slots1, p_rows = {}, {}
    for c in cats:
        first = feed[0][c]
        _, slots1 = rec["after_first"][c]
        at1 = _first_by_id(first, np.unique(first))
        p_slots1[c] = tuple(np.asarray(s)[at1] for s in slots1)
        last_rows = np.concatenate([np.asarray(r[c][0]) for r in rec["after_last"]])
        p_rows[c] = last_rows[_first_by_id(all_ids[c], uids[c])]
    program = cmp.readings(
        **side, losses=rec["losses"], slots1=p_slots1, rows=p_rows,
        dense_m1=rec["dense_m1"], dense=rec["dense_last"])

    fed = np.concatenate([traffic_lib.row_keys(b, cats) for b in feed])
    unknown = int((~np.isin(fed, written_keys)).sum()
                  + (len(fed) - len(np.unique(fed))))
    ok, compared = cmp.compare(program, reference, config["limits"],
                               extra={"feed_rows_unknown": unknown})
    if len(feed) != RECORDED_STEPS:
        ok = False
    detail = {k: cmp.leaf_gaps(program[k], reference[k])
              for k in ("grad_norm", "update_norm")}
    return ok, compared, detail


def window_numbers(window: dict, compiles: int, limits: dict) -> dict:
    """What the window itself is held to, beside the reference's comparison
    of the first steps: every step that was due was fed and applied
    (``due``/``fed``/``applied``: steps the data holds, batches the program's
    loop counted, ``state.step`` the window added), every epoch's mean loss is
    a number, and nothing compiled."""
    lost = (abs(window["due"] - window["fed"])
            + abs(window["fed"] - window["applied"])
            + sum(1 for x in window["losses"] if not np.isfinite(x)))
    return {
        "window_steps_lost": {"value": lost,
                              "limit": limits.get("window_steps_lost", 0)},
        "window_compiles": {"value": compiles, "limit": 0},
        "window_last_epoch_loss": {"value": window["losses"][-1],
                                   "limit": None},
    }


# -------------------------------------------------------------------- one run


def run(*, cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, devices, t_process_start: float,
        clock: monitor.CompileClock, sizes: dict | None = None,
        metric_readers=None, keep_trace: Path | None = None) -> dict:
    """One run of one cell.  ``sizes`` (tests only) replaces vocabularies,
    batch and epoch length; the code path is the same."""
    import jax

    sizes = sizes or {}
    on_tpu = devices[0].platform == "tpu"
    columns = {
        "categorical": dict(sizes.get("categorical", config["columns"]["categorical"])),
        "continuous": list(config["columns"]["continuous"]),
    }
    size_map = {config["columns"].get("size_map_keys", {}).get(c, c): v
                for c, v in columns["categorical"].items()}
    program = {**config["program"], **sizes.get("program", {})}
    config = {**config, "program": program}
    batch = int(program["per_device_train_batch_size"])
    data_shards = len(devices) // int(program.get("mesh", {}).get("model", 1))
    global_batch = batch * max(1, data_shards)
    epoch_steps = int(sizes.get("epoch_steps", traffic["epoch_steps"]))
    n_rows = epoch_steps * global_batch

    workdir = Path(tempfile.mkdtemp(prefix="bench_"))
    trainer = None
    try:
        rows = traffic_lib.draw_rows(seed, n_rows, columns=columns, traffic=traffic)
        traffic_lib.write_epoch(workdir / "data", rows, size_map,
                                files=int(traffic.get("files", 8)))
        written_keys = traffic_lib.row_keys(rows, list(columns["categorical"]))
        batch_bytes = sum(v.dtype.itemsize for v in rows.values()) * global_batch
        del rows
        cfg = build_config(config, data_dir=workdir / "data",
                           out_dir=workdir / "out", seed=seed, on_tpu=on_tpu)
        trainer = build_trainer(cfg, devices)
        if cfg.steps_per_execution != 1 or cfg.train.pipeline_overlap:
            raise ValueError("driver train_epoch records single-step calls: "
                             "steps_per_execution = 1, no pipeline_overlap")
        place = Placement(trainer, columns["categorical"], seed)
        flat = _paths(trainer.state.dense_params)
        stated = {k: tuple(v) for k, v in
                  config["reference"]["dense_shapes"].items()}
        built = {k: tuple(v.shape) for k, v in flat.items()}
        if built != stated:
            raise ValueError(f"the program built dense leaves {built}, the "
                             f"configuration states {stated}")
        install_weights(trainer, place, weights.dense_params(
            seed, {k: v.shape for k, v in flat.items()}))
        state_bytes = monitor.tree_bytes(trainer.state)
        dense_count = sum(int(np.prod(v.shape)) for v in flat.values())
        kernel_shapes = [tuple(v.shape) for v in flat.values() if v.ndim == 2]

        recorder = Recorder(trainer, make_reader(place), place)
        trainer.train_step = recorder
        trainer.train_epoch(0)               # warm-up: compiles the cell's step
        trainer.train_step = recorder.inner
        if recorder.calls != epoch_steps:
            raise RuntimeError(f"warm-up epoch took {recorder.calls} steps, "
                               f"the data holds {epoch_steps}")
        rec = recorder.fetch()
        del recorder
        setup_compiles, compile_s = clock.compiles, clock.seconds

        # ---- window
        trace_dir = workdir / "trace"
        t0 = monitor.now()
        setup_s = t0 - t_process_start
        epochs = 0
        fed0, applied0 = trainer._logged_steps, int(trainer.state.step)
        epoch_losses = []
        while True:
            epochs += 1
            with (traced_epoch(trainer, trace_dir, traffic)
                  if trace and epochs == 2 else contextlib.nullcontext()):
                epoch_losses.append(float(trainer.train_epoch(epochs)))
            elapsed = monitor.now() - t0
            if elapsed >= seconds and not (trace and epochs < 2):
                break
        window_compiles = clock.compiles - setup_compiles
        # the window's steps as the PROGRAM counted them: batches its loop fed
        # the step, and steps the state it left has applied (a guard rollback
        # rewinds the second; a step that returns its state unchanged never
        # moves it)
        steps = trainer._logged_steps - fed0
        applied = int(trainer.state.step) - applied0
        window = dict(due=epochs * epoch_steps, fed=steps, applied=applied,
                      losses=epoch_losses)
        rate = steps * global_batch / elapsed
        peak = monitor.peak_bytes(devices)

        ctx = None
        if trace:
            t1 = monitor.now()
            n_loader = 0
            last = None
            for b, k in trainer._train_batches(epochs + 1):
                n_loader += k
                last = b
            jax.block_until_ready(last)
            loader_rate = n_loader * global_batch / (monitor.now() - t1)
            ctx = dict(loader_examples_per_s=loader_rate,
                       compile_s=compile_s, peak_bytes=peak,
                       state_bytes=state_bytes, rate=rate,
                       batch=global_batch, dense_count=dense_count,
                       kernel_shapes=kernel_shapes, batch_bytes=batch_bytes,
                       n_chips=len(devices), config=config,
                       device_kind=devices[0].device_kind,
                       platform=devices[0].platform,
                       kind=place.kind, dim=int(cfg.embed_dim),
                       n_columns=len(columns["categorical"]),
                       unique_rows_per_step=float(np.mean([
                           sum(len(np.unique(np.asarray(b[c])))
                               for c in columns["categorical"])
                           for b in rec["batches"]])))

        tinfo = table_info(columns["categorical"], int(cfg.embed_dim), seed)
        trainer.logger.close()
        del trainer, place
        trainer = None
        gc.collect()

        t_ref = monitor.now()
        ok, compared, leaf_gaps = check(config, tinfo, columns["continuous"],
                                        rec, seed, written_keys)
        ref_s = monitor.now() - t_ref
        compared.update(window_numbers(window, window_compiles,
                                       config["limits"]))
        ok = ok and all(cmp.within(v) for v in compared.values())

        metrics: dict[str, dict] = {}
        breakdown = None
        device_extra: dict = {}
        if trace:
            from benchmarks.lib import trace as trace_lib

            if keep_trace is not None:  # tools only: a trace to read by hand
                shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
            planes = trace_lib.load(trace_dir)
            ctx["summary"] = trace_lib.summarise(planes, ctx["platform"])
            for name, unit, reader in metric_readers:
                value = reader(ctx)
                if value is not None:
                    metrics[name] = {"value": float(value), "unit": unit}
            s = ctx["summary"]
            if s is not None:
                device_extra = {"busy_s": s.busy_s, "window_s": s.window_s}
                host = [p for p in planes if p.name.startswith("/host:")]
                breakdown = {
                    "device_ops": trace_lib.top_ops(s.ops),
                    "idle_gaps": trace_lib.attribute_gaps(s.idle, host)}
        else:
            metrics = {
                "train_examples_per_s": {"value": rate, "unit": "examples/s"},
                "setup_s": {"value": setup_s, "unit": "s"},
            }
        print(f"benchmark: {cell['name']} seed {seed}: {epochs} epochs x "
              f"{epoch_steps} steps x {global_batch} examples in {elapsed:.3f} s "
              f"({rate:,.0f} examples/s); set-up {setup_s:.1f} s of which "
              f"compile {compile_s:.1f} s in {setup_compiles} programs "
              f"({clock.cache_hits} cache hits); reference {ref_s:.1f} s; "
              f"state {state_bytes / 2**30:.3f} GiB, peak {peak / 2**30:.3f} GiB",
              file=sys.stderr, flush=True)
        cmp.print_compared(compared, sys.stderr)
        result = {
            "correct": bool(ok), "attempted": steps,
            "failed": max(0, steps - applied), "metrics": metrics,
            "device": {"platform": devices[0].platform,
                       "kind": devices[0].device_kind, "count": len(devices),
                       "memory_peak_bytes": peak, **device_extra},
        }
        if breakdown is not None:
            result["breakdown"] = breakdown
        # for reading by hand; the driver ignores both
        result["leaf_gaps"] = leaf_gaps
        result["observed"] = {k: v["value"] for k, v in compared.items()
                              if v["limit"] is None}
        result["compared"] = {k: v for k, v in compared.items()
                              if v["limit"] is not None}
        return result
    finally:
        if trainer is not None:
            trainer.logger.close()
        shutil.rmtree(workdir, ignore_errors=True)
