"""Driver for the ``deepseek_v3`` training cells: the window is whole
``Trainer.train_epoch`` calls over packed token sequences, each trained by
its next-token loss.

The run is ``drivers/nemotron_epoch.py``'s, whose pieces this takes as they
are (weights: ``install_weights``, ``LazyWeights``, the selection bias
spread by ``BIAS_SPREAD``; the recorder, which keeps the counters the step
returns beside the loss; ``moe_rows``, ``window_pairs_per_step``) with
``drivers/lm_epoch.py``'s traffic (``draw_sequences``, ``write_epoch``,
``row_keys``), as ``drivers/sdar_epoch.py`` does.  What differs, because the
family does:

  * the attention's FLOPs are the (query, key) pairs its document-causal
    mask admits over the written documents (``lib/work_deepseek.py``),
    counted on the host and held to the program's own ``attn_pairs``
    counter step by step (``attn_pairs_uncounted``, exact);
  * the leaves the comparison groups are MLA's (``/attn/``) and the routed
    experts' (``/mlp/w1``, ``/mlp/w2`` of the expert layers).

What it takes from the program, by name, beside what ``train_epoch.py``
lists: ``Config.lm`` / ``Config.max_len``, the dense tree of
``models/deepseek_v3.py``, the collection's one table ``token_embedding``
under feature ``token``, the ``attn_pairs`` / ``moe_*`` counters its step
returns, the ``moe_*`` tallies of the epoch record
(``nemotron_epoch.epoch_tallies``), the scopes in ``SCOPES``,
``ops/moe.sorted_rows``."""

from __future__ import annotations

import contextlib
import gc
import importlib
import shutil
import statistics
import sys
import tempfile
from pathlib import Path

import numpy as np

from benchmarks.drivers import lm_epoch, nemotron_epoch
from benchmarks.drivers import train_epoch as base
from benchmarks.lib import compare as cmp
from benchmarks.lib import monitor, work_deepseek, work_lm

RECORDED_STEPS = base.RECORDED_STEPS
SCOPES = ("lm_embed", "mla_proj", "mla_rope", "mla_attn", "dense_mlp",
          "moe_route", "moe_experts", "moe_shared", "lm_head_loss",
          "emb_lookup", "dense_update", "emb_update")
TABLE = lm_epoch.TABLE
# configuration key (what this chip holds) -> the program's [lm] key that
# runs it: a published width under its own name, a count under ``*_held``
HELD_KEYS = {
    "hidden_size": "hidden_size", "vocab_size": "vocab_size",
    "num_hidden_layers": "num_hidden_layers",
    "num_attention_heads": "num_attention_heads",
    "kv_lora_rank": "kv_lora_rank", "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "intermediate_size": "intermediate_size",
    "first_k_dense_replace": "first_k_dense_replace",
    "n_routed_experts": "experts_held",
    "num_experts_per_tok": "num_experts_per_tok",
    "moe_intermediate_size": "moe_intermediate_size",
    "n_shared_experts": "n_shared_experts",
    "routed_scaling_factor": "routed_scaling_factor",
    "norm_topk_prob": "norm_topk_prob", "rms_norm_eps": "rms_norm_eps",
    "rope_theta": "rope_theta",
}


def reference_model(lm: dict) -> dict:
    """The reference's description of the model, from the program's ``lm``
    table: the same share (experts held, rows of the vocabulary)."""
    return dict(
        layers=int(lm["num_hidden_layers"]),
        heads=int(lm["num_attention_heads"]),
        kv_lora_rank=int(lm["kv_lora_rank"]),
        qk_nope_head_dim=int(lm["qk_nope_head_dim"]),
        qk_rope_head_dim=int(lm["qk_rope_head_dim"]),
        v_head_dim=int(lm["v_head_dim"]),
        first_k_dense_replace=int(lm.get("first_k_dense_replace", 0)),
        experts=int(lm.get("experts_held") or lm["n_routed_experts"]),
        first_expert_held=int(lm.get("first_expert_held", 0)),
        num_experts_per_tok=int(lm["num_experts_per_tok"]),
        norm_topk_prob=bool(lm.get("norm_topk_prob", True)),
        routed_scaling_factor=float(lm.get("routed_scaling_factor", 1.0)),
        rms_norm_eps=float(lm.get("rms_norm_eps", 1e-6)),
        rope_theta=float(lm["rope_theta"]), query_block=512)


def check(config: dict, lm: dict, shapes: dict, rec: dict, seed: int,
          written: np.ndarray, *, fault=None):
    """The program's readings from ``rec`` (``nemotron_epoch.Recorder``),
    the plain reference's over the same batches from the same weights,
    compared."""
    ref = importlib.import_module(
        f"benchmarks.reference.{config['reference']['module']}")
    optim = config["reference"]["optimizer"]
    feed = [{k: np.asarray(v) for k, v in b.items()} for b in rec["batches"]]
    table0 = lm_epoch.jit_leaf_values()(
        lm_epoch.leaf_key(seed, f"table:{TABLE}"),
        (int(lm["vocab_size"]), int(lm["hidden_size"])), "table")
    model = reference_model(lm)
    reference = ref.run_steps(model, optim,
                              nemotron_epoch.LazyWeights(seed, shapes), table0,
                              feed, fault=fault)
    b1 = {"dense": optim["dense"]["b1"], "table": optim["sparse"]["b1"]}
    program = {
        "losses": [float(x) for x in rec["losses"]],
        "grad_norm": {k: float(v) / (1.0 - b1[k.split(":")[0]])
                      for k, v in rec["m1"].items()},
        "update_norm": {k: float(v) for k, v in rec["moved"].items()}}
    fed = np.concatenate([lm_epoch.row_keys(b["token"], b["segment"])
                          for b in feed])
    counted = [int(c["attn_pairs"]) for c in rec["counters"]]
    extra = {"feed_rows_unknown": int((~np.isin(fed, written)).sum()
                                      + (len(fed) - len(np.unique(fed)))),
             "attn_pairs_uncounted": sum(
                 abs(c - model["layers"] * work_deepseek.admitted_pairs(
                     b["segment"])) for c, b in zip(counted, feed))}
    tallies = nemotron_epoch.epoch_tallies() or {}
    if tallies:
        extra["moe_pairs_uncomputed"] = abs(
            tallies["moe_pairs"] - tallies["moe_pairs_computed"])
    limits = config["limits"]
    ok, compared = cmp.compare(program, reference, limits, extra=extra)
    detail = {k: cmp.leaf_gaps(program[k], reference[k])
              for k in ("grad_norm", "update_norm")}
    # by the leaves a fault shows in (limits_why): latent attention's for a
    # wrong rotary layout, latent norm, scale or key; the routed experts'
    # for a wrongly scored or weighted pair; the median leaf for a fault of
    # every layer
    gaps = detail["grad_norm"]
    groups = {"grad_norm_gap_attn": [k for k in gaps if "/attn/" in k],
              "grad_norm_gap_experts": [k for k in gaps
                                        if k.endswith(("/mlp/w1", "/mlp/w2"))]}
    for name, leaves in groups.items():
        at = max(leaves, key=gaps.get)
        compared[name] = {"value": gaps[at], "limit": limits.get(name),
                          "leaf": at}
    compared["grad_norm_gap_median"] = {
        "value": statistics.median(gaps.values()),
        "limit": limits.get("grad_norm_gap_median")}
    # the routing, program (bfloat16 stream) beside reference (float32):
    # pairs of the recorded steps, by count
    got = [int(c["moe_pairs"]) for c in rec["counters"]]
    want = reference["moe_pairs"]
    compared["moe_pairs_agree"] = {
        "value": sum(map(min, got, want)) / max(1, sum(map(max, got, want))),
        "limit": None}
    expert_layers = model["layers"] - model["first_k_dense_replace"]
    mean_load = statistics.mean(want) / (expert_layers * model["experts"])
    compared["moe_load_max_over_mean"] = {
        "value": max(reference["moe_load_max"]) / max(mean_load, 1e-30),
        "limit": None}
    if tallies.get("steps"):
        compared["moe_layers_dense_per_step"] = {
            "value": tallies["moe_layers_dense"] / tallies["steps"],
            "limit": None}
    first = reference["moe_layer_loads"][0]
    detail["routing"] = {
        "moe_sorted_rows": nemotron_epoch.moe_rows(lm, feed[0]["token"].size),
        "moe_pairs_by_layer": [sum(layer) for layer in first],
        "moe_load_max_by_layer": [max(layer) for layer in first]}
    ok = (all(cmp.within(v) for v in compared.values())
          and len(feed) == RECORDED_STEPS)
    return ok, compared, detail


# ------------------------------------------------------------------ one run


def run(*, cell: dict, config: dict, traffic: dict, seed: int, seconds: float,
        trace: bool, devices, t_process_start: float,
        clock: monitor.CompileClock, sizes: dict | None = None,
        metric_readers=None, keep_trace: Path | None = None,
        faults: tuple = ()) -> dict:
    """One run of one cell.  ``sizes`` (tests only) replaces keys of the
    ``program`` table (its ``lm`` table merged key by key) and the epoch
    length; ``faults`` (tests and readings by hand only) are handed
    each to a further run of the reference over the same recorded steps,
    read under ``fault_readings``.  The code path is the same."""
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
        pairs = work_deepseek.admitted_pairs(segment) / len(segment)
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
        nemotron_epoch.install_weights(trainer, seed)
        state_bytes = monitor.tree_bytes(trainer.state)

        recorder = nemotron_epoch.Recorder(trainer, seed)
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
            t1 = monitor.now()
            n_loader, last = 0, None
            for b, k in trainer._train_batches(epochs + 1):
                n_loader += k
                last = b
            jax.block_until_ready(last)
            loader_rate = n_loader * global_batch / (monitor.now() - t1)
            model = reference_model(lm)
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
                       moe_pairs_per_step=nemotron_epoch.window_pairs_per_step(),
                       deepseek_shape=dict(
                           pairs_per_step=pairs * global_batch,
                           positions=tokens * global_batch,
                           layers=model["layers"], heads=model["heads"],
                           qk_head_dim=(model["qk_nope_head_dim"]
                                        + model["qk_rope_head_dim"]),
                           v_head_dim=model["v_head_dim"]))

        trainer.logger.close()
        del trainer
        trainer = None
        gc.collect()

        t_ref = monitor.now()
        ok, compared, leaf_gaps = check(config, lm, shapes, rec, seed, written)
        ref_s = monitor.now() - t_ref
        compared.update(base.window_numbers(window, window_compiles,
                                            config["limits"]))
        ok = ok and all(cmp.within(v) for v in compared.values())
        fault_readings = {
            f: {k: v["value"] for k, v in check(
                config, lm, shapes, rec, seed, written, fault=f)[1].items()}
            for f in faults}

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
        # for reading by hand; the driver ignores these
        result["leaf_gaps"] = {k: leaf_gaps[k]
                               for k in ("grad_norm", "update_norm")}
        result["observed"] = {**{k: v["value"] for k, v in compared.items()
                                 if v["limit"] is None},
                              **leaf_gaps["routing"]}
        result["compared"] = {k: v for k, v in compared.items()
                              if v["limit"] is not None}
        if fault_readings:
            result["fault_readings"] = fault_readings
        return result
    finally:
        if trainer is not None:
            trainer.logger.close()
        shutil.rmtree(workdir, ignore_errors=True)
