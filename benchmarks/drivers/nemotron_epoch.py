"""Driver for the ``nemotron_h`` training cells: the window is whole
``Trainer.train_epoch`` calls over packed token sequences.

The shape of a run is ``drivers/lm_epoch.py``'s, whose pieces this takes
where they serve unchanged (the traffic: ``draw_sequences``, ``write_epoch``,
``row_keys``; the hash of (seed, leaf, index): ``leaf_key``, ``leaf_values``)
as ``lm_epoch`` takes ``train_epoch``'s.  What differs, because the family
does:

  * the dense tree (``layer_<i>/norm``, ``layer_<i>/part/...``) has leaves
    of kinds ``lm_epoch`` does not know: the experts' 3-D leaves (the hash
    over the leaf flattened to two dimensions), the convolution's bias, the
    skip ``D`` and the router's selection bias, a seeded spread of
    ``+-BIAS_SPREAD`` that makes the held experts' loads uneven;
  * the step returns ``(loss, counters)``: the recorder keeps both, and the
    comparison holds ``moe_pairs_computed`` to ``moe_pairs`` over every
    epoch the run made (the program's own tallies in its epoch records).

What it takes from the program, by name, beside what ``train_epoch.py``
lists: ``Config.lm`` / ``Config.max_len``, the dense tree of
``models/nemotron_h.py``, the collection's one table ``token_embedding``
under feature ``token``, the ``moe_pairs`` / ``moe_pairs_computed`` /
``moe_load_max`` / ``moe_layers_dense`` tallies of the epoch record, the
scopes in ``SCOPES``, ``ops/ssd.CHUNK``, ``ops/moe.sorted_rows``."""

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
from pathlib import Path

import numpy as np

from benchmarks.drivers import lm_epoch
from benchmarks.drivers import train_epoch as base
from benchmarks.lib import compare as cmp
from benchmarks.lib import monitor, work_lm

RECORDED_STEPS = base.RECORDED_STEPS
SCOPES = ("lm_embed", "ssd_proj", "ssd_conv", "ssd_scan", "full_attn",
          "moe_route", "moe_latent", "moe_experts", "moe_shared",
          "lm_head_loss", "emb_lookup", "dense_update", "emb_update")
TABLE = lm_epoch.TABLE
BIAS_SPREAD = 0.1          # the selection bias: uniform in +-this
COUNTERS = ("moe_pairs", "moe_pairs_computed", "moe_load_max",
            "moe_layers_dense")
# configuration key (what this chip holds) -> the program's [lm] key that
# runs it: a published width under its own name, a count under ``*_held``
HELD_KEYS = {
    "hidden_size": "hidden_size", "vocab_size": "vocab_size",
    "hybrid_override_pattern": "hybrid_override_pattern",
    "mamba_num_heads": "mamba_heads_held", "mamba_head_dim": "mamba_head_dim",
    "ssm_state_size": "ssm_state_size", "conv_kernel": "conv_kernel",
    "num_attention_heads": "attention_heads_held", "head_dim": "head_dim",
    "n_routed_experts": "experts_held",
    "num_experts_per_tok": "num_experts_per_tok",
    "moe_latent_size": "moe_latent_size",
    "moe_intermediate_size": "moe_intermediate_size",
    "moe_shared_expert_intermediate_size": "moe_shared_expert_intermediate_size",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob", "layer_norm_epsilon": "rms_norm_eps",
}


# ------------------------------------------------------------------ weights


def leaf_kind(path: str) -> str:
    name = path.rsplit("/", 1)[-1]
    if name in ("router_bias", "conv_bias"):
        return name
    return "norm" if name == "D" else lm_epoch.leaf_kind(path)


def leaf_values(key, shape: tuple, kind: str):
    """``lm_epoch.leaf_values`` for the kinds it knows (a leaf of three
    dimensions hashed as its first two flattened); ``u`` uniform in [-1, 1)
    from the same hash for the rest: the selection bias ``BIAS_SPREAD u``,
    the convolution's bias ``0.1 u``; the skip ``D`` as a norm weight."""
    flat = (math.prod(shape[:-1]), shape[-1]) if len(shape) > 2 else shape
    if kind in ("router_bias", "conv_bias"):
        u = lm_epoch.leaf_values(key, flat, "table") / np.float32(lm_epoch.TABLE_SCALE)
        return u * np.float32(BIAS_SPREAD if kind == "router_bias" else 0.1)
    return lm_epoch.leaf_values(key, flat, kind).reshape(shape)


def install_weights(trainer, seed: int) -> None:
    """Replace the program's initial table and dense leaves by the
    benchmark's, each made in the buffer it replaces (``lm_epoch.
    install_weights`` with this module's kinds)."""
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
        return fn(old, lm_epoch.leaf_key(seed, path))

    flat = base._paths(state.dense_params)
    dense = base._unpaths({p: make(v, p, leaf_kind(p)) for p, v in flat.items()})
    if set(state.tables) != {TABLE} or state.tables[TABLE].ndim != 2:
        raise ValueError(f"nemotron_epoch: expected one plain table {TABLE!r}, "
                         f"the program holds {sorted(state.tables)}")
    tables = {TABLE: make(state.tables[TABLE], f"table:{TABLE}", "table")}
    trainer.state = dataclasses.replace(state, dense_params=dense,
                                        tables=tables)


class LazyWeights:
    """``mapping[top-level name]`` -> that subtree of the benchmark's dense
    weights, made on the device when asked (``lm_epoch.LazyWeights`` with
    this module's kinds)."""

    def __init__(self, seed: int, shapes: dict[str, tuple]):
        import jax

        self.seed, self.shapes = seed, shapes
        self.make = jax.jit(leaf_values, static_argnums=(1, 2))

    def __getitem__(self, top: str):
        sub = {p: s for p, s in self.shapes.items()
               if p == top or p.startswith(top + "/")}
        tree = base._unpaths({
            p: self.make(lm_epoch.leaf_key(self.seed, p), tuple(s), leaf_kind(p))
            for p, s in sub.items()})
        return tree[top]


# ----------------------------------------------------------------- recorder


class Recorder:
    """Stands where ``trainer.train_step`` stands for the warm-up epoch
    (``lm_epoch.Recorder``: the first batches and losses, small reductions
    of the state a step returned, never the state) and keeps the first
    steps' counters beside their losses."""

    def __init__(self, trainer, seed: int, steps: int = RECORDED_STEPS):
        import jax
        import jax.numpy as jnp

        self.inner = trainer.train_step
        self.steps, self.calls = steps, 0
        self.batches, self.losses, self.counters = [], [], []
        self.m1 = self.moved = None
        flat = base._paths(trainer.state.dense_params)
        keys = {p: lm_epoch.leaf_key(seed, p) for p in flat}
        tkey = lm_epoch.leaf_key(seed, f"table:{TABLE}")
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
            loss, counters = out[1]
            self.batches.append(batch)
            self.losses.append(loss)
            self.counters.append(counters)
            if self.calls == 1:
                self.m1 = self._first_moments(out[0])
            if self.calls == self.steps:
                self.moved = self._moved(out[0])
        return out

    def fetch(self) -> dict:
        import jax

        return jax.device_get(dict(
            batches=self.batches, losses=self.losses, counters=self.counters,
            m1=self.m1, moved=self.moved))


# -------------------------------------------------------------------- check


def reference_model(lm: dict, tokens: int) -> dict:
    """The reference's description of the model, from the program's ``lm``
    table: the same share (heads, groups and experts held, rows of the
    vocabulary)."""
    heads = int(lm.get("mamba_heads_held") or lm["mamba_num_heads"])
    per_group = int(lm["mamba_num_heads"]) // int(lm["n_groups"])
    q = int(lm.get("attention_heads_held") or lm["num_attention_heads"])
    per_kv = int(lm["num_attention_heads"]) // int(lm["num_key_value_heads"])
    return dict(
        pattern=str(lm["hybrid_override_pattern"]),
        mamba_heads=heads, mamba_groups=heads // per_group,
        mamba_head_dim=int(lm["mamba_head_dim"]),
        ssm_state_size=int(lm["ssm_state_size"]),
        attention_heads=q, key_value_heads=-(-q // per_kv),
        head_dim=int(lm["head_dim"]),
        n_routed_experts=int(lm["n_routed_experts"]),
        experts=int(lm.get("experts_held") or lm["n_routed_experts"]),
        first_expert_held=int(lm.get("first_expert_held", 0)),
        num_experts_per_tok=int(lm["num_experts_per_tok"]),
        routed_scaling_factor=float(lm.get("routed_scaling_factor", 1.0)),
        norm_topk_prob=bool(lm.get("norm_topk_prob", True)),
        rms_norm_eps=float(lm.get("rms_norm_eps", 1e-5)),
        token_block=min(128, tokens), query_block=min(512, tokens))


def epoch_tallies() -> dict[str, int] | None:
    """The program's own counters, summed over EVERY epoch this run made
    (the warm-up too): ``{name: total}`` and the ``steps`` they are over;
    nothing where the program keeps no epoch records."""
    try:
        from tdfo_tpu.obs.trace import epoch_history
    except ImportError:
        return None
    history = epoch_history()
    warm = max((i for i, r in enumerate(history) if r["epoch"] == 0), default=0)
    out = dict.fromkeys(COUNTERS, 0)
    out["steps"] = sum(int(r["steps"]) for r in history[warm:])
    for record in history[warm:]:
        for name in COUNTERS:
            out[name] += int(record.get("tallies", {}).get(name, (0, 0))[0])
    return out


def check(config: dict, lm: dict, shapes: dict, rec: dict, seed: int,
          written: np.ndarray, *, fault=None):
    """The program's readings from ``rec`` (``Recorder.fetch()``), the plain
    reference's over the same batches from the same weights, compared."""
    ref = importlib.import_module(
        f"benchmarks.reference.{config['reference']['module']}")
    optim = config["reference"]["optimizer"]
    feed = [{k: np.asarray(v) for k, v in b.items()} for b in rec["batches"]]
    tokens = feed[0]["token"].shape[1]
    table0 = lm_epoch.jit_leaf_values()(
        lm_epoch.leaf_key(seed, f"table:{TABLE}"),
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
    fed = np.concatenate([lm_epoch.row_keys(b["token"], b["segment"])
                          for b in feed])
    unknown = int((~np.isin(fed, written)).sum()
                  + (len(fed) - len(np.unique(fed))))
    limits = config["limits"]
    tallies = epoch_tallies() or {}
    extra = {"feed_rows_unknown": unknown}
    if tallies:
        extra["moe_pairs_uncomputed"] = abs(
            tallies["moe_pairs"] - tallies["moe_pairs_computed"])
    ok, compared = cmp.compare(program, reference, limits, extra=extra)
    detail = {k: cmp.leaf_gaps(program[k], reference[k])
              for k in ("grad_norm", "update_norm")}
    # by the leaves a fault shows in (limits_why): the attention layer's for
    # a document mask switched off, the routed experts' for a dropped or a
    # wrongly weighted pair, the median leaf for a fault of every layer
    gaps = detail["grad_norm"]
    kinds = dict(enumerate(str(lm["hybrid_override_pattern"])))
    of_kind = lambda kind: tuple(f"dense:layer_{i}/" for i, k in kinds.items()
                                 if k == kind)
    groups = {"grad_norm_gap_attn": [k for k in gaps if k.startswith(of_kind("*"))],
              "grad_norm_gap_experts": [k for k in gaps if k.startswith(of_kind("E"))
                                        and k.rsplit("/", 1)[-1] in ("w1", "w2")]}
    for name, leaves in groups.items():
        if leaves:
            at = max(leaves, key=gaps.get)
            compared[name] = {"value": gaps[at], "limit": limits.get(name),
                              "leaf": at}
    compared["grad_norm_gap_median"] = {
        "value": statistics.median(gaps.values()),
        "limit": limits.get("grad_norm_gap_median")}
    # the routing itself, program (bfloat16 products on the chip) beside
    # reference (float32): pairs of the recorded steps, by count
    got = [int(c["moe_pairs"]) for c in rec["counters"]]
    want = reference["moe_pairs"]
    compared["moe_pairs_agree"] = {
        "value": sum(map(min, got, want)) / max(1, sum(map(max, got, want))),
        "limit": None}
    model = reference_model(lm, tokens)
    mean_load = statistics.mean(want) / max(
        1, model["pattern"].count("E") * model["experts"])
    compared["moe_load_max_over_mean"] = {
        "value": max(reference["moe_load_max"]) / max(mean_load, 1e-30),
        "limit": None}
    # which form the expert layers took (ops/moe.py): the program's own
    # count over every step it made, and the first recorded step layer by
    # layer as the reference routes it
    if tallies.get("steps"):
        compared["moe_layers_dense_per_step"] = {
            "value": tallies["moe_layers_dense"] / tallies["steps"],
            "limit": None}
    first = reference["moe_layer_loads"][0]
    detail["routing"] = {
        "moe_sorted_rows": moe_rows(lm, feed[0]["token"].size),
        "moe_pairs_by_layer": [sum(layer) for layer in first],
        "moe_load_max_by_layer": [max(layer) for layer in first]}
    ok = (all(cmp.within(v) for v in compared.values())
          and len(feed) == RECORDED_STEPS)
    return ok, compared, detail


def moe_rows(lm: dict, tokens: int) -> int:
    """The static rows of the program's sorted form for ``tokens`` tokens a
    step: a layer whose pairs exceed them takes the dense form."""
    from tdfo_tpu.ops.moe import sorted_rows

    return sorted_rows(tokens, int(lm["num_experts_per_tok"]),
                       int(lm.get("experts_held") or lm["n_routed_experts"]),
                       int(lm["n_routed_experts"]))


def window_pairs_per_step() -> float | None:
    """``moe_pairs`` a step over the window's epochs, from the program's
    epoch records; nothing where the program keeps no such counter."""
    from benchmarks.lib import phases

    records = phases.window_epochs()
    if not records:
        return None
    pairs = sum(r.get("tallies", {}).get("moe_pairs", (0, 0))[0] for r in records)
    steps = sum(r["steps"] for r in records)
    return pairs / steps if pairs and steps else None


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
        for key, held in HELD_KEYS.items():
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
        token, segment = lm_epoch.draw_sequences(
            seed, epoch_steps * global_batch, tokens, int(lm["vocab_size"]),
            traffic)
        lm_epoch.write_epoch(workdir / "data", token, segment,
                             files=int(traffic.get("files", 2)))
        written = lm_epoch.row_keys(token, segment)
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
            from tdfo_tpu.ops.ssd import CHUNK  # the program's constant

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
                       tokens_per_s=lm_epoch.window_tokens_per_s(),
                       moe_pairs_per_step=window_pairs_per_step(),
                       nemotron_shape=dict(
                           tokens=tokens, chunk=CHUNK,
                           mamba_layers=model["pattern"].count("M"),
                           mamba_heads=model["mamba_heads"],
                           mamba_groups=model["mamba_groups"],
                           mamba_head_dim=model["mamba_head_dim"],
                           state=model["ssm_state_size"],
                           expert_layers=model["pattern"].count("E"),
                           experts=model["experts"],
                           latent=int(lm["moe_latent_size"]),
                           expert_width=int(lm["moe_intermediate_size"])))

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
        result["leaf_gaps"] = {k: leaf_gaps[k]
                               for k in ("grad_norm", "update_norm")}
        result["observed"] = {**{k: v["value"] for k, v in compared.items()
                                 if v["limit"] is None},
                              **leaf_gaps["routing"]}
        result["compared"] = {k: v for k, v in compared.items()
                              if v["limit"] is not None}
        return result
    finally:
        if trainer is not None:
            trainer.logger.close()
        shutil.rmtree(workdir, ignore_errors=True)
