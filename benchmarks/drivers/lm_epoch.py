"""Driver for sequence-model training cells: the window is whole
``Trainer.train_epoch`` calls over packed token sequences.

The shape of a run is ``drivers/train_epoch.py``'s, whose pieces this takes
where they fit (``build_config``, ``build_trainer``, ``traced_epoch``,
``window_numbers``): set-up (sequences from the seed -> ``Trainer`` -> the
benchmark's own weights from the seed -> ONE warm-up ``train_epoch`` whose
first steps are recorded) -> window -> memory peak -> state freed -> plain
reference on the chip, a layer at a time -> compare.  What differs, because
the dense state is many GiB and the step donates it:

  * the recorder keeps no array of the state.  Of step 1 it keeps the loss
    and, per leaf, the norm of the first Adam moment (the gradient:
    ``mu_1 = (1 - b1) g``); of step 3, per leaf, the norm of (parameters
    less the benchmark's initial weights, made again inside that program from
    (seed, leaf, index)).  Each is one small jitted reduction over the step's
    OUTPUT state, enqueued before the next step donates it.
  * the weights are made on the device leaf by leaf, into the program's own
    buffers, by a jitted function whose seed key is an ARGUMENT.

What it takes from the program, by name, beside what ``train_epoch.py``
lists: ``Config.lm`` / ``Config.max_len``, the dense tree of
``models/olmo_hybrid.py`` (``layer_<i>/...``, ``final_norm``, ``head``), the
collection's one table ``token_embedding`` under feature ``token``, the
``lm_tokens`` tally of the epoch record, the ``deltanet_scan`` / ``full_attn``
scopes, ``ops/gated_delta.CHUNK``."""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import math
import shutil
import statistics
import sys
import tempfile
import zlib
from pathlib import Path

import numpy as np

from benchmarks.drivers import train_epoch as base
from benchmarks.lib import compare as cmp
from benchmarks.lib import monitor, work_lm
from benchmarks.lib.weights import _fmix32, table_key

RECORDED_STEPS = base.RECORDED_STEPS
SCOPES = ("lm_embed", "deltanet_proj", "deltanet_conv", "deltanet_scan",
          "full_attn", "mlp", "lm_head_loss", "emb_lookup", "dense_update",
          "emb_update")
TABLE = "token_embedding"
TABLE_SCALE = 0.02          # the program's stated init (EmbeddingSpec)


# ------------------------------------------------------------------ traffic


def draw_sequences(seed: int, n: int, tokens: int, vocab: int, traffic: dict
                   ) -> tuple[np.ndarray, np.ndarray]:
    """``(token, segment)`` of ``[n, tokens]`` int32 from the seed: documents
    packed end to end, lengths log-uniform in the traffic mix's
    ``documents.min`` .. ``documents.max`` tokens, the last one of a sequence
    cut at its end; ids uniform over the vocabulary (slice).  Every seed
    gives the same shapes."""
    spec = traffic["documents"]
    if spec.get("distribution") != "log_uniform" or \
            traffic.get("ids", {}).get("distribution", "uniform") != "uniform":
        raise ValueError("lm_epoch: documents.distribution log_uniform and "
                         "ids.distribution uniform are what this driver draws")
    rng = np.random.default_rng([int(seed), 0x7B])
    lo = max(1, min(int(spec["min"]), tokens))
    hi = max(lo, min(int(spec["max"]), tokens))
    token = rng.integers(0, vocab, (n, tokens), dtype=np.int64).astype(np.int32)
    segment = np.zeros((n, tokens), np.int32)
    for row in segment:
        at = doc = 0
        while at < tokens:
            size = int(math.exp(rng.uniform(math.log(lo), math.log(hi + 1))))
            row[at:at + size] = doc
            at, doc = at + size, doc + 1
    return token, segment


def write_epoch(data_dir: Path, token, segment, files: int) -> None:
    import pyarrow as pa
    import pyarrow.parquet as pq

    (data_dir / "parquet_lm").mkdir(parents=True, exist_ok=True)
    cuts = np.linspace(0, len(token), files + 1).astype(int)
    for i, (a, b) in enumerate(zip(cuts, cuts[1:])):
        pq.write_table(pa.table({"token": list(token[a:b]),
                                 "segment": list(segment[a:b])}),
                       data_dir / "parquet_lm" / f"train_part_{i}.parquet")


def row_keys(token: np.ndarray, segment: np.ndarray) -> np.ndarray:
    """One number a sequence: enough to tell whether a row the program fed
    its step is one the generator wrote."""
    return np.array([zlib.crc32(t.tobytes() + s.tobytes())
                     for t, s in zip(np.ascontiguousarray(token, np.int32),
                                     np.ascontiguousarray(segment, np.int32))],
                    np.uint64)


# ------------------------------------------------------------------ weights


def leaf_kind(path: str) -> str:
    name = path.rsplit("/", 1)[-1]
    if name.endswith("norm"):
        return "norm"
    if name.startswith("conv_"):
        return "conv"
    return name if name in ("A_log", "dt_bias") else "proj"


def leaf_key(seed: int, path: str) -> np.uint32:
    return np.uint32(table_key(seed, path))


def leaf_values(key, shape: tuple, kind: str):
    """One leaf as a pure function of (key, index): ``u`` uniform in [-1, 1)
    from the hash ``lib/weights.py`` uses, mapped by the leaf's kind the way
    the program initialises it (``models/olmo_hybrid.init_leaf``): norm
    weights 1 + 0.1 u; projections, the head and the table 0.02 sqrt(3) u
    (standard deviation 0.02; the table 0.02 u, its stated init);
    convolutions u / sqrt(K); ``A_log = log(8.5 + 7.5 u)`` (A in [1, 16));
    ``dt_bias`` the inverse softplus of ``dt = 10^(-2 + u)``.  ``key`` is a
    traced uint32, so one compiled program serves every seed."""
    import jax.numpy as jnp

    rows, cols = (shape if len(shape) == 2 else (1, shape[0]))
    r = jnp.arange(rows, dtype=jnp.uint32)
    c = jnp.arange(cols, dtype=jnp.uint32)
    h = _fmix32(r * np.uint32(0x9E3779B1) + key.astype(jnp.uint32))
    h = _fmix32(h[:, None] ^ (c[None, :] * np.uint32(0x85EBCA77)
                              + np.uint32(0xC2B2AE3D)))
    u = ((h >> 8).astype(jnp.int32) - np.int32(1 << 23)).astype(jnp.float32) \
        * np.float32(1.0 / (1 << 23))
    u = u.reshape(shape)
    if kind == "norm":
        return 1.0 + 0.1 * u
    if kind == "conv":
        return u * np.float32(1.0 / math.sqrt(shape[0]))
    if kind == "A_log":
        return jnp.log(8.5 + 7.5 * u)
    if kind == "dt_bias":
        dt = jnp.exp(math.log(10.0) * (u - 2.0))
        return dt + jnp.log(-jnp.expm1(-dt))
    if kind == "table":
        return u * np.float32(TABLE_SCALE)
    return u * np.float32(0.02 * math.sqrt(3.0))


def jit_leaf_values():
    """``leaf_values`` jitted over a traced key, shape and kind static."""
    import jax

    return jax.jit(leaf_values, static_argnums=(1, 2))


def install_weights(trainer, seed: int) -> None:
    """Replace the program's initial table and dense leaves by the
    benchmark's, each made in the buffer it replaces (the old leaf is
    donated), one compiled program a distinct (shape, kind)."""
    import jax

    state = trainer.state
    made = {}

    def make(old, path, kind):
        fn = made.get((old.shape, kind))
        if fn is None:
            fn = made[(old.shape, kind)] = jax.jit(
                lambda old, key, shape=old.shape, kind=kind:
                leaf_values(key, shape, kind).astype(old.dtype),
                donate_argnums=(0,), out_shardings=old.sharding)
        return fn(old, leaf_key(seed, path))

    flat = base._paths(state.dense_params)
    dense = base._unpaths({p: make(v, p, leaf_kind(p)) for p, v in flat.items()})
    if set(state.tables) != {TABLE} or state.tables[TABLE].ndim != 2:
        raise ValueError(f"lm_epoch: expected one plain table {TABLE!r}, the "
                         f"program holds {sorted(state.tables)}")
    tables = {TABLE: make(state.tables[TABLE], f"table:{TABLE}", "table")}
    trainer.state = dataclasses.replace(state, dense_params=dense,
                                        tables=tables)


class LazyWeights:
    """``mapping[top-level name]`` -> that subtree of the benchmark's dense
    weights, made on the device when asked: the reference reads a subtree at
    the start and once more at the end, and never holds two copies."""

    def __init__(self, seed: int, shapes: dict[str, tuple]):
        self.seed, self.shapes = seed, shapes
        self.make = jit_leaf_values()

    def __getitem__(self, top: str):
        sub = {p: s for p, s in self.shapes.items()
               if p == top or p.startswith(top + "/")}
        tree = base._unpaths({p: self.make(leaf_key(self.seed, p), tuple(s),
                                           leaf_kind(p))
                              for p, s in sub.items()})
        return tree[top]


# ----------------------------------------------------------------- recorder


class Recorder:
    """Stands where ``trainer.train_step`` stands for the warm-up epoch.  It
    keeps the first batches and losses, and small reductions of the state a
    step returned (never the state: the next step donates it)."""

    def __init__(self, trainer, seed: int, steps: int = RECORDED_STEPS):
        import jax
        import jax.numpy as jnp

        self.inner = trainer.train_step
        self.steps, self.calls = steps, 0
        self.batches, self.losses = [], []
        self.m1 = self.moved = None
        flat = base._paths(trainer.state.dense_params)
        keys = {p: leaf_key(seed, p) for p in flat}
        tkey = leaf_key(seed, f"table:{TABLE}")
        norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

        @jax.jit
        def first_moments(state):
            mu = next(s.mu for s in state.opt_state if hasattr(s, "mu"))
            out = {f"dense:{p}": norm(v) for p, v in base._paths(mu).items()}
            out["table:token"] = norm(state.slots[TABLE][0])
            return out

        @jax.jit
        def moved(state, keys, tkey):
            out = {f"dense:{p}": norm(v - leaf_values(keys[p], v.shape,
                                                      leaf_kind(p)))
                   for p, v in base._paths(state.dense_params).items()}
            t = state.tables[TABLE]
            out["table:token"] = norm(t - leaf_values(tkey, t.shape, "table"))
            return out

        self._first_moments = first_moments
        self._moved = lambda state: moved(state, keys, tkey)

    def __call__(self, state, batch, *rest):
        out = self.inner(state, batch, *rest)
        self.calls += 1
        if self.calls <= self.steps:
            self.batches.append(batch)
            self.losses.append(out[1])
            if self.calls == 1:
                self.m1 = self._first_moments(out[0])
            if self.calls == self.steps:
                self.moved = self._moved(out[0])
        return out

    def fetch(self) -> dict:
        import jax

        return jax.device_get(dict(batches=self.batches, losses=self.losses,
                                   m1=self.m1, moved=self.moved))


# -------------------------------------------------------------------- check


def reference_model(lm: dict, tokens: int) -> dict:
    """The reference's description of the model, from the program's ``lm``
    table: the same share (heads held, rows of the vocabulary)."""
    heads = int(lm["num_attention_heads"])
    return dict(
        layer_types=list(lm["layer_types"]),
        head_dim=int(lm["hidden_size"]) // heads,
        full_heads=int(lm.get("full_heads_held") or heads),
        linear_heads=int(lm.get("linear_heads_held") or heads),
        linear_key_head_dim=int(lm["linear_key_head_dim"]),
        linear_value_head_dim=int(lm["linear_value_head_dim"]),
        linear_allow_neg_eigval=bool(lm.get("linear_allow_neg_eigval", True)),
        rms_norm_eps=float(lm.get("rms_norm_eps", 1e-6)),
        token_block=min(128, tokens), query_block=min(512, tokens))


def check(config: dict, lm: dict, shapes: dict, rec: dict, seed: int,
          written: np.ndarray, *, fault=None):
    """The program's readings from ``rec`` (``Recorder.fetch()``), the plain
    reference's over the same batches from the same weights, compared."""
    ref = importlib.import_module(
        f"benchmarks.reference.{config['reference']['module']}")
    optim = config["reference"]["optimizer"]
    feed = [{k: np.asarray(v) for k, v in b.items()} for b in rec["batches"]]
    tokens = feed[0]["token"].shape[1]
    table0 = jit_leaf_values()(
        leaf_key(seed, f"table:{TABLE}"),
        (int(lm["vocab_size"]), int(lm["hidden_size"])), "table")
    reference = ref.run_steps(reference_model(lm, tokens), optim,
                              LazyWeights(seed, shapes), table0, feed,
                              fault=fault)
    b1 = {"dense": optim["dense"]["b1"], "table": optim["sparse"]["b1"]}
    program = {
        "losses": [float(x) for x in rec["losses"]],
        "grad_norm": {k: float(v) / (1.0 - b1[k.split(":")[0]])
                      for k, v in rec["m1"].items()},
        "update_norm": {k: float(v) for k, v in rec["moved"].items()}}
    fed = np.concatenate([row_keys(b["token"], b["segment"]) for b in feed])
    unknown = int((~np.isin(fed, written)).sum()
                  + (len(fed) - len(np.unique(fed))))
    limits = config["limits"]
    ok, compared = cmp.compare(program, reference, limits,
                               extra={"feed_rows_unknown": unknown})
    detail = {k: cmp.leaf_gaps(program[k], reference[k])
              for k in ("grad_norm", "update_norm")}
    # the worst leaf of all is heavy-tailed over seeds (q and k of the
    # delta-rule layers, bfloat16 products against the float32 reference).
    # The full-attention layers' leaves are not, and they are where a wrong
    # document mask shows; the median leaf moves with a fault of every layer
    gaps = detail["grad_norm"]
    full = tuple(f"dense:layer_{i}/" for i, kind in enumerate(lm["layer_types"])
                 if kind == "full_attention")
    at = max((k for k in gaps if k.startswith(full)), key=gaps.get, default=None)
    if at is not None:
        compared["grad_norm_gap_full_attn"] = {
            "value": gaps[at], "limit": limits.get("grad_norm_gap_full_attn"),
            "leaf": at}
    compared["grad_norm_gap_median"] = {
        "value": statistics.median(gaps.values()),
        "limit": limits.get("grad_norm_gap_median")}
    ok = (all(cmp.within(v) for v in compared.values())
          and len(feed) == RECORDED_STEPS)
    return ok, compared, detail


def window_tokens_per_s() -> float | None:
    """``lm_tokens`` over ``loop_s``, summed over the window's epochs, from
    the program's own epoch records; nothing where the program keeps no such
    counter."""
    from benchmarks.lib import phases

    records = phases.window_epochs()
    if not records:
        return None
    tokens = sum(r.get("tallies", {}).get("lm_tokens", (0, 0))[0]
                 for r in records)
    seconds = sum(r["loop_s"] for r in records)
    return tokens / seconds if tokens and seconds > 0 else None


# ------------------------------------------------------------------ one run


def run(*, cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, devices, t_process_start: float,
        clock: monitor.CompileClock, sizes: dict | None = None,
        metric_readers=None, keep_trace: Path | None = None,
        fault=None) -> dict:
    """One run of one cell.  ``sizes`` (tests only) replaces keys of the
    ``program`` table (its ``lm`` table merged key by key) and the epoch
    length; ``fault`` (tests and the builder's readings only) is handed to
    the reference.  The code path is the same."""
    import jax

    sizes = sizes or {}
    on_tpu = devices[0].platform == "tpu"
    over = dict(sizes.get("program", {}))
    program = {**config["program"], **over,
               "lm": {**config["program"]["lm"], **over.get("lm", {})}}
    config = {**config, "program": program}
    lm = program["lm"]
    if not sizes:
        for key, held in (("hidden_size", "hidden_size"),
                          ("intermediate_size", "intermediate_size"),
                          ("vocab_size", "vocab_size"),
                          ("num_attention_heads", "full_heads_held"),
                          ("linear_num_value_heads", "linear_heads_held"),
                          ("linear_key_head_dim", "linear_key_head_dim"),
                          ("linear_value_head_dim", "linear_value_head_dim"),
                          ("layer_types", "layer_types")):
            if config[key] != lm[held]:
                raise ValueError(f"configuration states {key} = {config[key]!r}"
                                 f", its program.lm runs {held} = {lm[held]!r}")
    tokens = int(program["max_len"])
    batch = int(program["per_device_train_batch_size"])
    global_batch = batch * max(1, len(devices)
                               // int(program.get("mesh", {}).get("model", 1)))
    epoch_steps = int(sizes.get("epoch_steps", traffic["epoch_steps"]))

    workdir = Path(tempfile.mkdtemp(prefix="bench_"))
    trainer = None
    try:
        token, segment = draw_sequences(seed, epoch_steps * global_batch,
                                        tokens, int(lm["vocab_size"]), traffic)
        write_epoch(workdir / "data", token, segment,
                    files=int(traffic.get("files", 2)))
        written = row_keys(token, segment)
        batch_bytes = (token.itemsize + segment.itemsize) * tokens * global_batch
        del token, segment
        cfg = base.build_config(config, data_dir=workdir / "data",
                                out_dir=workdir / "out", seed=seed,
                                on_tpu=on_tpu)
        trainer = base.build_trainer(cfg, devices)
        flat = base._paths(trainer.state.dense_params)
        shapes = {p: tuple(v.shape) for p, v in flat.items()}
        dense_count = sum(int(np.prod(s)) for s in shapes.values())
        del flat
        install_weights(trainer, seed)
        state_bytes = monitor.tree_bytes(trainer.state)

        recorder = Recorder(trainer, seed)
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
            with (base.traced_epoch(trainer, trace_dir, traffic)
                  if trace and epochs == 2 else contextlib.nullcontext()):
                epoch_losses.append(float(trainer.train_epoch(epochs)))
            elapsed = monitor.now() - t0
            if elapsed >= seconds and not (trace and epochs < 2):
                break
        window_compiles = clock.compiles - setup_compiles
        steps = trainer._logged_steps - fed0
        applied = int(trainer.state.step) - applied0
        window = dict(due=epochs * epoch_steps, fed=steps, applied=applied,
                      losses=epoch_losses)
        rate = steps * global_batch / elapsed
        peak = monitor.peak_bytes(devices)

        ctx = None
        if trace:
            from tdfo_tpu.ops.gated_delta import CHUNK  # the program's constant

            t1 = monitor.now()
            n_loader, last = 0, None
            for b, k in trainer._train_batches(epochs + 1):
                n_loader += k
                last = b
            jax.block_until_ready(last)
            loader_rate = n_loader * global_batch / (monitor.now() - t1)
            model = reference_model(lm, tokens)
            ctx = dict(loader_examples_per_s=loader_rate, compile_s=compile_s,
                       peak_bytes=peak, state_bytes=state_bytes, rate=rate,
                       batch=global_batch, n_columns=tokens,
                       dense_count=dense_count, batch_bytes=batch_bytes,
                       kernel_shapes=work_lm.dense_kernel_shapes(shapes, tokens),
                       n_chips=len(devices), config=config,
                       device_kind=devices[0].device_kind,
                       platform=devices[0].platform,
                       kind=trainer.state.sparse_opt.kind,
                       dim=int(lm["hidden_size"]),
                       unique_rows_per_step=float(np.mean(
                           [len(np.unique(np.asarray(b["token"])))
                            for b in rec["batches"]])),
                       tokens_per_s=window_tokens_per_s(),
                       lm_shape=dict(
                           tokens=tokens, chunk=CHUNK,
                           linear_layers=model["layer_types"].count(
                               "linear_attention"),
                           linear_heads=model["linear_heads"],
                           dk=model["linear_key_head_dim"],
                           dv=model["linear_value_head_dim"]))

        trainer.logger.close()
        del trainer
        trainer = None
        gc.collect()

        t_ref = monitor.now()
        ok, compared, leaf_gaps = check(config, lm, shapes, rec, seed, written,
                                        fault=fault)
        ref_s = monitor.now() - t_ref
        compared.update(base.window_numbers(window, window_compiles,
                                            config["limits"]))
        ok = ok and all(cmp.within(v) for v in compared.values())

        metrics: dict[str, dict] = {}
        breakdown = None
        device_extra: dict = {}
        if trace:
            from benchmarks.lib import scopes, trace as trace_lib

            if keep_trace is not None:  # tools only: a trace to read by hand
                shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
            planes = trace_lib.load(trace_dir)
            ctx["summary"] = trace_lib.summarise(planes, ctx["platform"])
            ctx["scope_ms"] = (scopes.scope_ms(trace_dir, SCOPES)
                               if ctx["summary"] is not None else None)
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
                    "idle_gaps": trace_lib.attribute_gaps(s.idle, host),
                    "scope_ms": ctx["scope_ms"]}
        else:
            metrics = {
                "train_examples_per_s": {"value": rate, "unit": "examples/s"},
                "setup_s": {"value": setup_s, "unit": "s"},
            }
        print(f"benchmark: {cell['name']} seed {seed}: {epochs} epochs x "
              f"{epoch_steps} steps x {global_batch} sequences of {tokens} "
              f"tokens in {elapsed:.3f} s ({rate:,.3f} examples/s); set-up "
              f"{setup_s:.1f} s of which compile {compile_s:.1f} s in "
              f"{setup_compiles} programs ({clock.cache_hits} cache hits); "
              f"reference {ref_s:.1f} s; state {state_bytes / 2**30:.3f} GiB, "
              f"peak {peak / 2**30:.3f} GiB", file=sys.stderr, flush=True)
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
