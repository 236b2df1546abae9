"""Single entry point: ``python -m tdfo_tpu.launch --config config.toml``.

Replaces the reference's per-backend script zoo (``python train.py`` /
``train_dp.py`` / ``train_ps.py``; ``torchx run ... dist.ddp -j 1x2``,
``torchrec/README.md:56``).  On a TPU pod every host runs this same command;
where the environment describes several processes
``jax.distributed.initialize()`` discovers the peers from it — no TF_CONFIG /
cluster.json / torchx env plumbing (SURVEY.md §5.6).  A single process (one
host, 1 or 4 chips) initialises nothing.

Subcommands:
  * ``train`` (default)      — build the Trainer from config and fit.
  * ``serve``                — export the newest checkpoint to a serving
    bundle and run the micro-batching scoring frontend (+ a retrieval round
    for TwoTower and Bert4Rec; bert4rec configs serve the SEQ family —
    ragged histories bucketed into masked-position candidate scoring,
    ``tdfo_tpu/serve/seq_scoring.py``); ``[serving] replicas > 1`` runs a
    multi-replica fleet
    over one bundle store with per-replica request logs
    (``tdfo_tpu/serve/fleet.py``); knobs live in the ``[serving]`` table.
  * ``online``               — close the loop: replay the frontend's request
    log (``[serving] log_features``) into incremental training cycles, each
    ending in a delta export + hot swap (``tdfo_tpu/train/online.py``);
    with ``[online] canary_cycles > 0`` every candidate is shadow-scored on
    held-out replayed traffic, canaried on a fraction of the serving fleet
    and auto-rolled-back on AUC regression; knobs live in ``[online]``.
    ``[serving] fleet_mode = "process"`` runs the fleet as real OS
    processes behind a socket ingress with a respawning supervisor
    (``tdfo_tpu/serve/supervisor.py``).
  * ``serve-fleet``          — export a bundle and stand up the
    out-of-process fleet (N ``serve/replica_main.py`` children behind the
    power-of-two-choices ingress), then push a synthetic trace through it;
    the process twin of ``serve`` with ``[serving] replicas > 1``.
  * ``loadgen``              — drive the out-of-process fleet with zipf
    traffic (``[loadgen]``: open/closed loop, concurrency, rate) sweeping
    the load axis to the latency/throughput knee
    (``tdfo_tpu/serve/loadgen.py``).
  * ``plan``                 — price every per-table embedding placement
    against the measured cost model (``tdfo_tpu/plan``) using the
    preprocessing ``table_stats.json`` and write ``sharding_plan.json``;
    knobs live in the ``[planner]`` config table.
  * ``obs``                  — assemble the causal trace sinks written by a
    ``[telemetry] trace = true`` run (``trace-*.jsonl`` under
    checkpoint_dir/log_dir) into per-cycle timelines, freshness lag and
    fleet latency histograms (``tdfo_tpu/obs/aggregate.py``); writes a
    ``chrome_trace.json`` loadable in ``chrome://tracing`` / Perfetto.
  * ``preprocess-ctr``       — TwoTower ETL (jax-flax/preprocessing parity).
  * ``preprocess-seq``       — Bert4Rec ETL (torchrec/preprocessing parity).
  * ``preprocess-criteo``    — Criteo-format ETL (BASELINE.json DLRM family).
  * ``synth``                — write a synthetic raw-goodreads fixture.
  * ``synth-criteo``         — write a synthetic Criteo train.txt fixture.
"""

from __future__ import annotations

import argparse
import sys


def _init_distributed(flag: str) -> None:
    """``--distributed``: ``never`` initialises nothing; ``auto`` initialises
    only where a multi-process environment is described
    (``core/mesh.multiprocess_env``) — a single process never calls
    ``jax.distributed.initialize()``, whose auto-detection may wait on a
    metadata service a sealed one-host machine does not have; ``always``
    demands such an environment.  An initialisation that was asked for and
    fails is fatal."""
    if flag == "never":
        return
    from tdfo_tpu.core.mesh import initialize_distributed

    if initialize_distributed():
        return
    if flag == "always":
        raise SystemExit(
            "--distributed always, but no multi-process environment is "
            "described: set WORLD_SIZE / RANK / COORDINATOR_ADDRESS (or run "
            "where jax's TPU pod variables are set)")
    print("single-process run (no multi-process environment described; "
          "jax.distributed not initialised)")


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(prog="tdfo_tpu.launch", description=__doc__)
    p.add_argument("command", nargs="?", default="train",
                   choices=["train", "serve", "serve-fleet", "loadgen",
                            "online", "plan", "obs",
                            "preprocess-ctr", "preprocess-seq",
                            "preprocess-criteo", "synth", "synth-criteo"])
    p.add_argument("--config", default="config.toml", help="path to config.toml")
    p.add_argument("--data-dir", default=None, help="override config data_dir")
    p.add_argument("--distributed", default="auto", choices=["auto", "always", "never"],
                   help="jax.distributed.initialize policy (multi-host pods)")
    p.add_argument("--log-dir", default=None)
    args = p.parse_args(argv)

    from tdfo_tpu.core.config import read_configs

    overrides = {}
    if args.data_dir:
        overrides["data_dir"] = args.data_dir
    cfg = read_configs(args.config, **overrides)

    if args.command == "synth":
        from tdfo_tpu.data.synthetic import write_synthetic_goodreads

        write_synthetic_goodreads(cfg.data_dir)
        print(f"synthetic goodreads raw files written to {cfg.data_dir}")
        return 0
    if args.command == "synth-criteo":
        from tdfo_tpu.data.synthetic import write_synthetic_criteo

        write_synthetic_criteo(cfg.data_dir)
        print(f"synthetic criteo train.txt written to {cfg.data_dir}")
        return 0
    if args.command == "preprocess-criteo":
        from tdfo_tpu.data.criteo_preprocessing import run_criteo_preprocessing

        size_map = run_criteo_preprocessing(
            cfg.data_dir, seed=cfg.seed,
            hot_vocab=cfg.embeddings.hot_vocab,
            hot_fraction=cfg.embeddings.hot_fraction,
        )
        print(f"size_map: {{{len(size_map)} tables, "
              f"max vocab {max(size_map.values())}}}")
        return 0
    if args.command == "preprocess-ctr":
        from tdfo_tpu.data.ctr_preprocessing import run_ctr_preprocessing

        size_map = run_ctr_preprocessing(
            cfg.data_dir, seed=cfg.seed, write_format=cfg.write_format,
            hot_vocab=cfg.embeddings.hot_vocab,
            hot_fraction=cfg.embeddings.hot_fraction,
        )
        print(f"size_map: {size_map}")
        return 0
    if args.command == "plan":
        # pure host work: price placements from the stats artifact and the
        # measured cost table — no devices, no distributed init needed
        from tdfo_tpu.plan.planner import format_plan, plan_tables, write_plan
        from tdfo_tpu.plan.stats import load_table_stats

        if cfg.model not in ("dlrm", "twotower"):
            raise SystemExit(
                f"the planner targets the DMP sparse regimes (dlrm / "
                f"twotower), not model = {cfg.model!r}")
        stats = load_table_stats(cfg.data_dir)
        if stats is None:
            raise SystemExit(
                f"no table_stats.json under {cfg.data_dir} — re-run "
                "preprocessing (preprocess-ctr / preprocess-criteo) to "
                "emit the traffic-stats artifact")
        served = set(cfg.categorical_features or ())
        if served:
            stats = {k: v for k, v in stats.items() if k in served}
        plan = plan_tables(
            stats,
            dim=cfg.embed_dim,
            # the step's id traffic is the GLOBAL batch: every device's
            # rows funnel into the same sharded tables
            batch_size=cfg.per_device_train_batch_size
            * cfg.planner.n_devices,
            optimizer=cfg.sparse_optimizer,
            dense_model="twotower" if cfg.model == "twotower" else "dlrm",
            n_devices=cfg.planner.n_devices,
            hbm_gb=cfg.planner.hbm_gb,
            slot_dtype=cfg.embeddings.slot_dtype,
        )
        path = write_plan(cfg.data_dir, plan)
        print(format_plan(plan))
        print(f"plan written to {path}")
        return 0
    if args.command == "obs":
        # pure host work: fold the trace sinks of a finished (or killed)
        # traced run into one causal report — no devices, no distributed
        # init needed
        import json
        from pathlib import Path

        from tdfo_tpu.obs.aggregate import (assemble, chrome_trace,
                                            format_report, load_spans)

        out_dir = args.log_dir or cfg.checkpoint_dir
        if not out_dir:
            raise SystemExit(
                "obs needs the traced run's output dir — set checkpoint_dir "
                "in the config or pass --log-dir")
        trace_dir = Path(out_dir) / "trace"
        spans = load_spans(trace_dir)
        if not spans:
            raise SystemExit(
                f"no trace-*.jsonl spans under {trace_dir} — run with "
                "[telemetry] trace = true first")
        report = assemble(spans)
        print(format_report(report))
        chrome_path = trace_dir / "chrome_trace.json"
        chrome_path.write_text(json.dumps(chrome_trace(spans)))
        print(f"chrome trace written to {chrome_path} "
              "(load in chrome://tracing or ui.perfetto.dev)")
        return 0
    if args.command == "preprocess-seq":
        from tdfo_tpu.data.seq_preprocessing import run_seq_preprocessing

        stats = run_seq_preprocessing(
            cfg.data_dir, max_len=cfg.max_len, sliding_step=cfg.sliding_step,
            mask_prob=cfg.mask_prob, seed=cfg.seed, pad=not cfg.jagged,
        )
        print(f"seq preprocessing: {stats}")
        return 0

    from tdfo_tpu.core.mesh import configure_compile_cache

    configure_compile_cache()
    _init_distributed(args.distributed)

    if cfg.model == "bert4rec":
        # bert4rec has its OWN handshake file with remapped 1-based ids
        # (torchrec parity); the CTR size_map.json that read_configs auto-merges
        # counts the full catalog and would mis-size the mask token.
        import json
        from pathlib import Path

        alt = Path(cfg.data_dir) / "size_map_bert4rec.json"
        if alt.exists():
            cfg = cfg.replace(size_map=json.loads(alt.read_text()))
    if cfg.faults.any():
        # a [faults] section deliberately kills/corrupts this run (test
        # harness, tdfo_tpu/utils/faults.py) — make that impossible to miss
        # in the launch log of a run that mysteriously dies with exit 17
        print(f"WARNING: fault injection armed: {cfg.faults}", flush=True)
    if args.command in ("serve", "serve-fleet", "loadgen", "online"):
        # explicit model-kind dispatch: resolve the serving family ONCE at
        # the entry point so an unsupported model dies here with the family
        # map (CTR = twotower/dlrm, seq = bert4rec) instead of deep in a
        # scorer traceback
        from tdfo_tpu.core.config import serving_model_kind

        try:
            kind = serving_model_kind(cfg)
        except ValueError as e:
            raise SystemExit(str(e)) from None
        print(f"{args.command}: model {cfg.model!r} -> "
              f"{kind} serving family", flush=True)
    if args.command == "serve":
        from tdfo_tpu.serve.frontend import serve_from_config

        stats = serve_from_config(cfg, log_dir=args.log_dir)
        print({k: round(v, 5) if isinstance(v, float) else v
               for k, v in stats.items()})
        return 0
    if args.command == "serve-fleet":
        from tdfo_tpu.serve.loadgen import serve_fleet_from_config

        stats = serve_fleet_from_config(cfg, log_dir=args.log_dir)
        print({k: round(v, 5) if isinstance(v, float) else v
               for k, v in stats.items()})
        return 0
    if args.command == "loadgen":
        from tdfo_tpu.serve.loadgen import loadgen_from_config

        report = loadgen_from_config(cfg, log_dir=args.log_dir)
        for s in report["steps"]:
            axis = (f"conc={s['concurrency']}" if s["mode"] == "closed"
                    else f"rate={s['offered_qps']:.1f}qps")
            p99 = "-" if s["p99_ms"] is None else f"{s['p99_ms']:.2f}ms"
            print(f"loadgen {s['mode']} {axis}: "
                  f"qps={s['achieved_qps']:.1f} p99={p99} "
                  f"shed={s['shed']} failed={s['failed']} "
                  f"slo_ok={s['slo_ok']}")
        knee = report["knee"]
        print("knee: none (no step met the p99 SLO)" if knee is None else
              f"knee: qps={knee['achieved_qps']:.1f} at p99="
              f"{knee['p99_ms']:.2f}ms (SLO {knee['p99_slo_ms']} ms)")
        return 0
    if args.command == "online":
        from tdfo_tpu.train.online import online_from_config

        stats = online_from_config(cfg, log_dir=args.log_dir)
        print({k: round(v, 5) if isinstance(v, float) else v
               for k, v in stats.items()})
        return 0

    from tdfo_tpu.train.trainer import Trainer

    metrics = Trainer(cfg, log_dir=args.log_dir).fit()
    print({k: round(v, 5) for k, v in metrics.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
