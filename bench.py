"""Benchmark harness — prints ONE JSON line with the headline metric.

Headline: CTR train-step throughput in the sparse/DMP regime (TwoTower by
default; ``--model dlrm`` for the BASELINE.json north-star family;
``--dense`` for the reference-parity dense regime), examples/sec/chip on the
real device, plus MFU, HBM utilisation vs the roofline floor, the 100M-row
big-table demo, and the embedding lookup latency microbench (gspmd vs
explicit psum vs all-to-all programs — the BASELINE.json metric family).

Measurement discipline — an inherited method, to be re-validated (ROADMAP S0):

  * a timing must end in a sync (``jax.block_until_ready`` or a value fetch):
    jax returns before the device finishes, so a bare per-step wall clock
    measures dispatch, not compute — the round-1 failure mode (42M
    examples/sec/chip, 6x beyond the memory roofline).
  * the recipe used here: compile a ``lax.scan`` chain of K steps into one
    executable, force completion with a scalar value fetch, and measure two
    chain lengths — ``step_time = (T(K2) - T(K1)) / (K2 - K1)`` cancels every
    constant per-call cost (dispatch, the fetch).  Each rep feeds a fresh
    on-device batch stack so no two timed executions are identical.

  Whether this or plain ``block_until_ready`` timing is the honest clock on
  the sealed one-host machine builders measure on now is S0's measurement;
  until then chain differencing stays as it is.

  An HBM-roofline sanity floor is computed from the optimizer's minimum
  memory traffic; the harness REFUSES to report a step time that beats the
  roofline (exit 1) instead of printing an impossible number.

``vs_baseline`` compares against ``BENCH_BASELINE.json`` (auto-written on
first accepted run; the reference publishes no numbers — BASELINE.md — so
the baseline is this framework's first honest measurement).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

# device_kind substring -> (peak bf16 TFLOP/s, HBM GB/s) per chip.
# Public spec-sheet numbers (v5e: 197 bf16 TFLOPs, 819 GB/s).
CHIP_SPECS = {
    "v5 lite": (197.0, 819.0),
    "v5e": (197.0, 819.0),
    "v5p": (459.0, 2765.0),
    "v6": (918.0, 1640.0),
    "v4": (275.0, 1228.0),
    "v3": (123.0, 900.0),
}
_DEFAULT_SPEC = (197.0, 819.0)

SIZE_MAP = {
    "user": 500_000, "item": 200_000, "language": 32, "is_ebook": 2,
    "format": 16, "publisher": 5_000, "pub_decade": 16,
}

# Criteo-Kaggle per-column vocabulary sizes (the standard 26-table profile
# used by the public DLRM benchmarks) — 33.76M embedding rows total, the
# BASELINE.json "DLRM-Criteo examples/sec/chip" workload.
CRITEO_KAGGLE_VOCABS = (
    1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3, 93145, 5683,
    8351593, 3194, 27, 14992, 5461306, 10, 5652, 2173, 4, 7046547, 18, 15,
    286181, 105, 142572,
)


def chip_peaks() -> tuple[float, float, bool]:
    """(peak bf16 TFLOP/s, HBM GB/s, spec_assumed).  ``spec_assumed`` is True
    when the device kind is unrecognised and the v5e fallback was used — MFU /
    HBM-utilisation numbers are then approximate and the record says so."""
    import jax

    kind = jax.devices()[0].device_kind.lower()
    for key, spec in CHIP_SPECS.items():
        if key in kind:
            return (*spec, False)
    print(
        f"bench: unrecognised device_kind {kind!r}; assuming v5e peaks "
        f"{_DEFAULT_SPEC} — MFU/HBM-utilisation and the roofline guard are "
        "approximate for this chip",
        file=sys.stderr,
    )
    return (*_DEFAULT_SPEC, True)


def _make_host_batch(rng: np.random.Generator, b: int) -> dict[str, np.ndarray]:
    return {
        "user_id": rng.integers(0, SIZE_MAP["user"], b, dtype=np.int32),
        "item_id": rng.integers(0, SIZE_MAP["item"], b, dtype=np.int32),
        "language": rng.integers(0, SIZE_MAP["language"], b, dtype=np.int32),
        "is_ebook": rng.integers(0, 2, b, dtype=np.int32),
        "format": rng.integers(0, SIZE_MAP["format"], b, dtype=np.int32),
        "publisher": rng.integers(0, SIZE_MAP["publisher"], b, dtype=np.int32),
        "pub_decade": rng.integers(0, SIZE_MAP["pub_decade"], b, dtype=np.int32),
        "avg_rating": rng.random(b, dtype=np.float32),
        "num_pages": rng.random(b, dtype=np.float32),
        "label": rng.integers(0, 2, b).astype(np.float32),
    }


def dense_flops_per_example(params) -> float:
    """Model FLOPs per example for a training step: 2*m*n per dense kernel
    forward, x3 for fwd + both backward matmuls (standard MFU accounting;
    embedding gathers contribute no matmul FLOPs)."""
    import jax

    fwd = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if "kernel" in name and leaf.ndim == 2:
            fwd += 2.0 * leaf.shape[0] * leaf.shape[1]
    return 3.0 * fwd


def chain_time(run, make_args, ks: tuple[int, int] = (5, 45), reps: int = 3) -> float:
    """Per-step seconds via chain-length differencing.

    ``run(k)`` -> a compiled fn of ``make_args(k, seed)`` outputs returning a
    scalar; each timed call gets fresh args (unique execution) and is forced
    by the float() fetch.  Returns the median over per-rep differenced
    estimates — robust to host-clock outliers.
    """
    k1, k2 = ks
    times: dict[int, list[float]] = {k1: [], k2: []}
    for k in (k1, k2):
        fn = run(k)
        warm = make_args(k, seed=k)
        float(fn(*warm))  # compile + warm (not timed)
        for rep in range(reps):
            args = make_args(k, seed=1000 + 10 * k + rep)
            t0 = time.perf_counter()
            float(fn(*args))
            times[k].append(time.perf_counter() - t0)
    diffs = sorted(
        (t2 - t1) / (k2 - k1) for t1, t2 in zip(times[k1], times[k2])
    )
    return diffs[len(diffs) // 2]


def _stack_batches(mesh, host: dict, k: int, b: int):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    stack = {
        kk: jax.device_put(
            v.reshape(k, b, *v.shape[1:]),
            NamedSharding(mesh, P(None, "data")),
        )
        for kk, v in host.items()
    }
    # force EVERY leaf's host->device transfer to finish OUTSIDE the
    # timed window (transfer cost scales with k just like compute, so
    # the differencing would not cancel it)
    float(sum(jnp.sum(v.astype(jnp.float32)) for v in stack.values()))
    return stack


def build_train_bench(batch_size: int, embed_dim: int):
    """Dense regime (reference parity): nn.Embed tables + dense AdamW.

    Kept as the comparison path; the headline is the sparse/DMP regime below,
    whose optimizer traffic is O(batch) instead of O(vocab)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh
    from tdfo_tpu.models.twotower import init_twotower
    from tdfo_tpu.train.state import TrainState, make_adamw
    from tdfo_tpu.train.step import make_train_step

    platform = jax.devices()[0].platform
    dtype = jnp.bfloat16 if platform != "cpu" else jnp.float32
    model, params = init_twotower(jax.random.key(0), SIZE_MAP, embed_dim, dtype=dtype)
    mesh = make_mesh(MeshSpec(data=-1, model=1, seq=1))
    state = jax.device_put(
        TrainState.create(apply_fn=model.apply, params=params, tx=make_adamw(3e-4, 1e-4)),
        NamedSharding(mesh, P()),
    )
    b = batch_size * mesh.shape["data"]

    # inner step WITHOUT donation: every chained execution must be free to
    # start from the same persistent state buffers.
    inner = make_train_step(mesh=mesh, donate_state=False)
    # unjitted twin for the one-off counters probe (a collector cannot see
    # through an inner jit boundary)
    probe_inner = make_train_step(mesh=mesh, donate_state=False, jit=False)

    def counters_probe(seed: int = 7) -> dict[str, float]:
        from tdfo_tpu.obs import counters as obs_counters

        @jax.jit
        def one(state, batch):
            with obs_counters.collect() as c:
                _, loss = probe_inner(state, batch)
            return loss, dict(c)

        host = _make_host_batch(np.random.default_rng(seed), b)
        stack = _stack_batches(mesh, host, 1, b)
        _, ctrs = one(state, {k: v[0] for k, v in stack.items()})
        return {k: round(float(v), 3) for k, v in ctrs.items()}

    def run(k):
        @jax.jit
        def chain(state, stack):
            final, losses = jax.lax.scan(lambda st, bt: inner(st, bt), state, stack)
            return losses[-1]

        return lambda stack: chain(state, stack)

    def make_args(k, seed):
        r = np.random.default_rng(seed)
        host = _make_host_batch(r, b * k)
        return (_stack_batches(mesh, host, k, b),)

    # roofline: dense AdamW must read+write params/mu/nu every step (6x param
    # bytes) — an irreducible HBM-traffic floor for this optimizer.  (Forward/
    # backward param reads and gradient traffic come on top; excluding them
    # keeps this a true lower bound.)
    param_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(state.params))
    floor_bytes = 6.0 * param_bytes
    flops_per_example = dense_flops_per_example(state.params)
    return run, make_args, b, floor_bytes, flops_per_example, counters_probe


# Why the sparse headline sits far above its BYTE-roofline floor: the floor
# prices touched-row traffic at full HBM bandwidth, but row-granular access
# on v5e is DESCRIPTOR- and SORT-RATE bound, not bandwidth bound.  Round-4
# ablation on the real chip (step ~1.17 ms total): fwd+bwd+dense-optax
# ~0.38 ms, the five one-hot small-table updates ~0.11 ms, and the STACKED
# fat-table group (user+item in one array, one launch) ~0.8 ms = ~0.24 ms
# dedupe (single-sort formulation, ops/sparse.py:dedupe_grads — the round-3
# figure was ~2 ms across two per-table jnp.unique + default-searchsorted
# dedupes) + ~0.57 ms for the in-place row-DMA kernel on ~16k touched rows
# x 2 directions.  The per-descriptor cost is the hardware floor for
# scattered single-row access on this chip generation (the dedicated
# SparseCore units on larger TPUs exist precisely for this); the byte floor
# is kept as the REFUSAL threshold because it is the only bound that is
# provably irreducible.


def _make_criteo_host_batch(rng: np.random.Generator, b: int,
                            powerlaw: bool = False) -> dict[str, np.ndarray]:
    if powerlaw:
        from tdfo_tpu.data.synthetic import zipf_ids

        out: dict[str, np.ndarray] = {
            f"cat_{i}": zipf_ids(rng, v, b)
            for i, v in enumerate(CRITEO_KAGGLE_VOCABS)
        }
    else:
        out = {
            f"cat_{i}": rng.integers(0, v, b, dtype=np.int32)
            for i, v in enumerate(CRITEO_KAGGLE_VOCABS)
        }
    for i in range(13):
        out[f"cont_{i}"] = rng.random(b, dtype=np.float32)
    out["label"] = rng.integers(0, 2, b).astype(np.float32)
    return out


def build_criteo_train_bench(batch_size: int, embed_dim: int,
                             hot_vocab: int = 0, powerlaw: bool = False,
                             fused_threshold: int | None = None):
    """DLRM over the Criteo-Kaggle table profile (26 tables, 33.76M rows):
    the BASELINE.json north-star metric measured directly.  Big tables live
    in ONE fused rowwise-adagrad fat-line stack (4 packed rows per 128-lane
    line; in-place DMA kernel update — no XLA scatter in the step), small
    tables in one plain 2D stack; dedup_lookup shares one sort between the
    forward gather and the update (fbgemm fused-TBE parity, the huge-table
    configuration: one f32 accumulator per row).

    ``hot_vocab > 0`` enables the frequency-partitioned hot/cold mode
    (``parallel/embedding.py``): every table's ``[0, min(hot_vocab, V))``
    prefix — the Criteo-ETL frequency-ranked layout — becomes a replicated
    hot head updated scatter-free via one-hot MXU contractions, and the
    batches switch to power-law (zipf-ranked) ids so the lookup traffic
    concentrates on the head like real Criteo traffic does.  ``powerlaw``
    alone keeps the single-table layout under the same skewed traffic —
    the honest ablation baseline.

    ``fused_threshold`` overrides the storage/update path for the big
    tables: ``None`` (default) keeps everything in plain 2D stacks — the
    measured-fastest layout for this profile — while a vocab threshold
    routes the tables above it into the fused rowwise-adagrad fat-line
    stack (the config-defaults build; the planner bench's "defaults" arm).
    """
    import jax
    import jax.numpy as jnp

    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh
    from tdfo_tpu.models.dlrm import DLRMBackbone, generic_embedding_specs
    from tdfo_tpu.ops.sparse import sparse_optimizer
    from tdfo_tpu.parallel.embedding import ShardedEmbeddingCollection
    from tdfo_tpu.train.ctr import ctr_sparse_forward
    from tdfo_tpu.train.sparse_step import SparseTrainState, make_sparse_train_step

    platform = jax.devices()[0].platform
    dtype = jnp.bfloat16 if platform != "cpu" else jnp.float32
    mesh = make_mesh(MeshSpec(data=-1, model=1, seq=1))
    cats = tuple(f"cat_{i}" for i in range(26))
    conts = tuple(f"cont_{i}" for i in range(13))
    size_map = {c: v for c, v in zip(cats, CRITEO_KAGGLE_VOCABS)}
    # Plain stacked tables measured FASTER than fused fat-line storage for
    # this profile (22.5 vs ~29 ms/step): at ~100k scattered row-touches the
    # XLA row scatter (~10 ms at the deduped 101k-slot bound) beats the
    # per-line DMA kernel + its operand routing, while the fat layout's
    # 512B line granularity also taxes the forward gather.  The fused path
    # remains the right choice for memory-bound tables (optimizer state
    # packed in-line) and for small touch counts (twotower d=64 adam);
    # docs/BUDGET.md carries the full measured decomposition.
    powerlaw = powerlaw or hot_vocab > 0
    hot_ids = None
    if hot_vocab > 0:
        hot_ids = {c: np.arange(min(hot_vocab, v), dtype=np.int32)
                   for c, v in size_map.items()}
    coll = ShardedEmbeddingCollection(
        generic_embedding_specs(size_map, cats, embed_dim, "row",
                                fused_threshold=fused_threshold),
        mesh=mesh, stack_tables=True, fused_kind="rowwise_adagrad",
        hot_ids=hot_ids,
    )
    # shapes only — the real tables are built INSIDE the jitted chain (a
    # per-chain constant the differencing cancels): an 8.65 GB table passed
    # as a chain ARGUMENT would need disjoint input+output copies (~17 GB,
    # OOM); zeroed in-chain tables alias through the scan carry and row-RMW
    # timing is content-independent (cf. bench_big_table).
    table_shapes = jax.eval_shape(coll.init, jax.random.key(0))
    backbone = DLRMBackbone(embed_dim=embed_dim, dtype=dtype,
                            cat_columns=cats, cont_columns=conts)
    dummy_embs = {f: jnp.zeros((1, embed_dim), jnp.float32)
                  for f in coll.features()}
    dummy_cont = {c: jnp.zeros((1,)) for c in conts}
    import optax

    dense = backbone.init(jax.random.key(1), dummy_embs, dummy_cont)["params"]
    opt = sparse_optimizer("rowwise_adagrad", lr=3e-4)
    b = batch_size * mesh.shape["data"]
    inner = make_sparse_train_step(
        coll, ctr_sparse_forward(backbone), jit=False, donate=False,
        dedup_lookup=True,
    )

    def run(k):
        @jax.jit
        def chain(dense, stack):
            tables = {n: jnp.zeros(sh.shape, sh.dtype)
                      for n, sh in table_shapes.items()}
            state = SparseTrainState.create(
                dense_params=dense,
                tx=optax.adamw(3e-4, weight_decay=1e-4),
                tables=tables,
                sparse_opt=opt,
            )
            final, losses = jax.lax.scan(lambda st, bt: inner(st, bt), state, stack)
            return losses[-1]

        return lambda stack: chain(dense, stack)

    def counters_probe(seed: int = 7) -> dict[str, float]:
        # one counters-on step (telemetry registry riding the real step):
        # per-table touched/unique rows + grad/param norms in the record.
        # The TIMED chain above stays counters-off — byte-identical program.
        from tdfo_tpu.obs import counters as obs_counters

        @jax.jit
        def one(dense, batch):
            tables = {n: jnp.zeros(sh.shape, sh.dtype)
                      for n, sh in table_shapes.items()}
            state = SparseTrainState.create(
                dense_params=dense,
                tx=optax.adamw(3e-4, weight_decay=1e-4),
                tables=tables, sparse_opt=opt)
            with obs_counters.collect() as c:
                _, loss = inner(state, batch)
            return loss, dict(c)

        r = np.random.default_rng(seed)
        host = _make_criteo_host_batch(r, b, powerlaw=powerlaw)
        stack = _stack_batches(mesh, host, 1, b)
        _, ctrs = one(dense, {k: v[0] for k, v in stack.items()})
        return {k: round(float(v), 3) for k, v in ctrs.items()}

    unique_rows_per_step: list[float] = []
    hot_k = {c: coll.hot_count(f"{c}_embed") for c in cats}
    hot_info = {
        "enabled": hot_vocab > 0, "hot_vocab": hot_vocab,
        "powerlaw": powerlaw,
        "fully_hot_tables": sum(coll.hot_full(f"{c}_embed") for c in cats),
        "hit_rates": [],
    }

    def make_args(k, seed):
        r = np.random.default_rng(seed)
        host = _make_criteo_host_batch(r, b * k, powerlaw=powerlaw)
        ids = {c: host[c].reshape(k, b) for c in cats}
        for step in range(k):
            # COLD uniques only: hot hits never reach the scatter path, so
            # the roofline floor must not charge row traffic for them
            unique_rows_per_step.append(float(sum(
                len(np.unique(v[step][v[step] >= hot_k[c]]))
                for c, v in ids.items()
            )))
        if hot_vocab > 0:
            # lookup-mass fraction landing on the hot heads (power-law
            # traffic concentrates here — the number the split banks on)
            hits = sum(int((v < hot_k[c]).sum()) for c, v in ids.items())
            hot_info["hit_rates"].append(hits / (len(cats) * k * b))
        return (_stack_batches(mesh, host, k, b),)

    dense_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(dense))
    flops_per_example = dense_flops_per_example(dense)

    def floor_bytes_fn() -> float:
        # the fused update reads+writes packed 128-lane lines (table rows +
        # accumulator cells together); best case every touched row shares
        # its line fully -> w lanes x 4B x 2 directions per row.  Plus the
        # dense 6x AdamW sweep, and — in hot/cold mode — the hot heads'
        # dense masked RMW (whole [K, D] table + [K] rowwise accumulator,
        # read and write, every step).
        from tdfo_tpu.ops.pallas_kernels import line_layout

        lay = line_layout(embed_dim, "rowwise_adagrad")
        u_mean = float(np.mean(unique_rows_per_step)) if unique_rows_per_step else 0.0
        hot_bytes = sum(2.0 * 4.0 * (k_ * embed_dim + k_)
                        for k_ in hot_k.values())
        return 2.0 * u_mean * lay.w * 4.0 + 6.0 * dense_bytes + hot_bytes

    return (run, make_args, b, floor_bytes_fn, flops_per_example, hot_info,
            counters_probe)


def bench_planner_dlrm(batch_size: int, embed_dim: int, *,
                       on_tpu: bool,
                       headline_step_ms: float | None = None) -> dict:
    """Planner-chosen vs all-defaults placement on the DLRM-Criteo profile
    (the ``planner_dlrm8`` record).

    The auto-sharding planner (``tdfo_tpu/plan``) prices every per-table
    placement from the measured v5e cost table over the SAME uniform-id
    traffic this benchmark generates (uniform per-id counts -> occupancy
    uniques, exactly the ``_make_criteo_host_batch`` distribution).  The
    predicted numbers are pure host math and always present; the measured
    arms (chain-differenced like the headline) run on TPU only:

      * ``step_ms_default`` — what the config defaults build: fused
        fat-line storage for every table above the 16384-row threshold;
      * ``step_ms_chosen`` — the planner's placement.  On this profile the
        planner keeps the big tables PLAIN (docs/BUDGET.md: 22.4 vs
        29-32 ms measured), so when no big table chose fused the arm is the
        headline configuration and reuses its measurement instead of
        re-timing a byte-identical program (a rerun would only add
        noise).

    Hot-head choices are priced into the prediction but NOT rebuilt in the
    measured arms — the storage/update-path decision is the arm under test;
    the hot-split payoff is measured separately (``--hot-vocab`` /
    ``record["hot_cold"]``).
    """
    import jax

    from tdfo_tpu.plan import plan_digest, plan_tables, table_stats_from_counts
    from tdfo_tpu.plan.planner import FUSED_MIN_VOCAB

    b = batch_size * max(1, jax.device_count())
    stats = {f"cat_{i}": table_stats_from_counts(np.ones(v, np.int64))
             for i, v in enumerate(CRITEO_KAGGLE_VOCABS)}
    plan = plan_tables(stats, dim=embed_dim, batch_size=b,
                       optimizer="rowwise_adagrad", dense_model="dlrm",
                       n_devices=1)
    tables = plan["tables"]
    rec = {
        "plan_digest": plan_digest(plan),
        "predicted_chosen_ms": plan["predicted_step_ms"],
        "predicted_default_ms": plan["predicted_default_ms"],
        "predicted_speedup": round(
            plan["predicted_default_ms"] / plan["predicted_step_ms"], 3),
        "fused_tables": int(sum(t["fused"] for t in tables.values())),
        "hot_tables": int(sum(t["hot_k"] > 0 for t in tables.values())),
        "bf16_tables": int(sum(t["dtype"] == "bfloat16"
                               for t in tables.values())),
    }
    if not on_tpu:
        return rec
    run_d, make_args_d, *_ = build_criteo_train_bench(
        batch_size, embed_dim, fused_threshold=FUSED_MIN_VOCAB)
    rec["step_ms_default"] = round(chain_time(run_d, make_args_d) * 1e3, 3)
    big_fused = any(t["vocab"] > FUSED_MIN_VOCAB and t["fused"]
                    for t in tables.values())
    if not big_fused and headline_step_ms is not None:
        rec["step_ms_chosen"] = round(headline_step_ms, 3)
        rec["chosen_is_headline"] = True
    else:
        run_c, make_args_c, *_ = build_criteo_train_bench(
            batch_size, embed_dim,
            fused_threshold=FUSED_MIN_VOCAB if big_fused else None)
        rec["step_ms_chosen"] = round(chain_time(run_c, make_args_c) * 1e3, 3)
    rec["measured_speedup"] = round(
        rec["step_ms_default"] / rec["step_ms_chosen"], 3)
    return rec


def build_sparse_train_bench(batch_size: int, embed_dim: int,
                             model: str = "twotower",
                             table_dtype: str = "float32"):
    """HEADLINE: the DMP regime — ShardedEmbeddingCollection + row-sparse
    in-backward Adam (``make_sparse_train_step``), the torchrec
    ``DistributedModelParallel`` + fused-optimizer equivalent.  ``model``
    picks the CTR head: "twotower" or "dlrm" (the BASELINE.json north-star
    family — feature-interaction head over the same 7 tables).

    Roofline floor recomputed for the sparse path: the optimizer only
    read-modify-writes the TOUCHED rows of table/mu/nu (6 x unique-rows x D x
    4B per table, measured from the actual benchmark batches) plus the dense
    tower params — per-step traffic is O(batch), not O(vocab), which is
    exactly the capability the dense path lacked (VERDICT r2 Missing #2).
    """
    import jax
    import jax.numpy as jnp

    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh
    from tdfo_tpu.models.twotower import TwoTowerBackbone, ctr_embedding_specs
    from tdfo_tpu.ops.sparse import sparse_optimizer
    from tdfo_tpu.parallel.embedding import ShardedEmbeddingCollection
    from tdfo_tpu.train.ctr import ctr_sparse_forward
    from tdfo_tpu.train.sparse_step import SparseTrainState, make_sparse_train_step

    platform = jax.devices()[0].platform
    dtype = jnp.bfloat16 if platform != "cpu" else jnp.float32
    mesh = make_mesh(MeshSpec(data=-1, model=1, seq=1))
    specs = ctr_embedding_specs(SIZE_MAP, embed_dim, "row")
    if table_dtype != "float32":
        # quantized STORAGE (bf16/int8 tables + stochastic-rounding writes);
        # compute stays f32 either way, so the step program only differs by
        # the storage width and the SR key threading.  int8 rows carry a
        # per-row (scale, offset) sidecar and never ride fat lines, so the
        # int8 arm rebuilds the specs plain
        import dataclasses as _dc

        if table_dtype == "int8":
            specs = ctr_embedding_specs(SIZE_MAP, embed_dim, "row",
                                        fused_threshold=None)
        specs = [_dc.replace(s, dtype=jnp.dtype(table_dtype)) for s in specs]
    coll = ShardedEmbeddingCollection(specs, mesh=mesh)
    tables = coll.init(jax.random.key(0))
    table_bytes = int(sum(t.nbytes for t in tables.values()))
    if model == "dlrm":
        from tdfo_tpu.models.dlrm import DLRMBackbone

        backbone = DLRMBackbone(embed_dim=embed_dim, dtype=dtype)
    else:
        backbone = TwoTowerBackbone(embed_dim=embed_dim, dtype=dtype)
    dummy_embs = {f: jnp.zeros((1, embed_dim), jnp.float32) for f in coll.features()}
    dummy_cont = {"avg_rating": jnp.zeros((1,)), "num_pages": jnp.zeros((1,))}
    import optax

    dense = backbone.init(jax.random.key(1), dummy_embs, dummy_cont)["params"]
    state = SparseTrainState.create(
        dense_params=dense,
        tx=optax.adamw(3e-4, weight_decay=1e-4),
        tables=tables,
        sparse_opt=sparse_optimizer("adam", lr=3e-4, weight_decay=1e-4),
    )
    b = batch_size * mesh.shape["data"]
    # no dedup_lookup here: at ~8k touched rows/step the shared-sort
    # machinery costs more than it saves (measured 2.08 vs 1.3 ms/step);
    # dedup pays off at the Criteo profile's ~100k touches
    inner = make_sparse_train_step(
        coll, ctr_sparse_forward(backbone), jit=False, donate=False
    )

    def counters_probe(seed: int = 7) -> dict[str, float]:
        from tdfo_tpu.obs import counters as obs_counters

        @jax.jit
        def one(state, batch):
            with obs_counters.collect() as c:
                _, loss = inner(state, batch)
            return loss, dict(c)

        host = _make_host_batch(np.random.default_rng(seed), b)
        stack = _stack_batches(mesh, host, 1, b)
        _, ctrs = one(state, {k: v[0] for k, v in stack.items()})
        return {k: round(float(v), 3) for k, v in ctrs.items()}

    def run(k):
        @jax.jit
        def chain(state, stack):
            final, losses = jax.lax.scan(lambda st, bt: inner(st, bt), state, stack)
            return losses[-1]

        return lambda stack: chain(state, stack)

    unique_rows_per_step: list[float] = []

    def make_args(k, seed):
        r = np.random.default_rng(seed)
        host = _make_host_batch(r, b * k)
        # exact touched-row counts for the roofline floor, from the real data
        # (the id columns are exactly the features the collection serves)
        ids = {c: host[c].reshape(k, b) for c in coll.features()}
        for step in range(k):
            unique_rows_per_step.append(
                float(sum(len(np.unique(v[step])) for v in ids.values()))
            )
        return (_stack_batches(mesh, host, k, b),)

    dense_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(dense))
    flops_per_example = dense_flops_per_example(dense)

    t_item = jnp.dtype(table_dtype).itemsize

    def floor_bytes_fn() -> float:
        # sparse Adam read-modify-writes table/mu/nu rows for touched rows
        # only: table rows at the STORAGE dtype width (read + write), mu/nu
        # slots at f32 (4 passes), U measured per step above; dense params
        # still pay the full 6x dense AdamW sweep (they're tiny).
        u_mean = float(np.mean(unique_rows_per_step)) if unique_rows_per_step else 0.0
        per_row = 2.0 * t_item + 4.0 * 4.0
        return per_row * u_mean * embed_dim + 6.0 * dense_bytes

    return (run, make_args, b, floor_bytes_fn, flops_per_example, table_bytes,
            counters_probe)


def bench_embedding_lookup(batch_size: int = 8192, vocab: int = 2_000_000,
                           dim: int = 128) -> dict:
    """Median latency of the three embedding-lookup programs on the real mesh,
    measured by the same chain-differencing (a scan of dependent lookups).

    Single-chip caveat: on one chip the model axis has a single shard, so the
    collectives are degenerate — the number measures the lookup *program*
    (gather + bucketing/permute overhead), reported with ``n_shards`` so it
    is never mistaken for a multi-chip ICI measurement.  The multi-chip path
    is validated separately by the driver's ``dryrun_multichip``.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh
    from tdfo_tpu.parallel.embedding import EmbeddingSpec, ShardedEmbeddingCollection

    mesh = make_mesh(MeshSpec(data=1, model=-1, seq=1))
    n_shards = mesh.shape["model"]
    coll = ShardedEmbeddingCollection(
        [EmbeddingSpec("table", vocab, dim, features=("ids",), sharding="row")],
        mesh=mesh,
    )
    tables = coll.init(jax.random.key(0))

    out: dict[str, object] = {}
    for mode in ("gspmd", "psum", "alltoall"):
        # feed each program the id sharding its shard_map declares: alltoall
        # wants ids sharded over the model axis (torchrec regime); psum wants
        # them replicated — a mismatched layout would time an artifact
        # resharding collective, not the lookup
        ids_spec = P(None, "model") if (mode == "alltoall" and n_shards > 1) else P()

        def run(k, mode=mode):
            @jax.jit
            def chain(tables, ids_stack):
                def body(carry, ids):
                    # fold the carry into the ids so each lookup depends on
                    # the previous one's result — scan can't overlap them
                    ids = (ids + carry.astype(jnp.int32)) % vocab
                    vecs = coll.lookup(tables, {"ids": ids}, mode=mode)["ids"]
                    return jnp.abs(vecs).sum().astype(jnp.float32) % 1024, None

                final, _ = jax.lax.scan(body, jnp.float32(0), ids_stack)
                return final

            return lambda stack: chain(tables, stack)

        def make_args(k, seed, ids_spec=ids_spec):
            r = np.random.default_rng(seed)
            ids = r.integers(0, vocab, (k, batch_size)).astype(np.int32)
            stack = jax.device_put(ids, NamedSharding(mesh, ids_spec))
            float(jnp.sum(stack))
            return (stack,)

        # us-scale ops need long chains so the signal (hundreds of chained
        # lookups) clears the per-fetch host-clock noise
        sec = chain_time(run, make_args, ks=(64, 512), reps=3)
        out[mode] = round(sec * 1e6, 1)  # us

    # The grouped exchange's claim is per-TABLE collective elimination
    # (2 all_to_all per step regardless of table count vs 2 per table), so
    # its honest baseline is a MULTI-table spec: same total vocab split
    # over n_tables, per-table alltoall vs one grouped exchange.
    n_tables = 8
    tv = vocab // n_tables
    specs = [
        EmbeddingSpec(f"t{i}", tv, dim, features=(f"ids{i}",), sharding="row")
        for i in range(n_tables)
    ]
    for key, grouped in (("alltoall_per_table8", False),
                         ("alltoall_grouped8", True)):
        mcoll = ShardedEmbeddingCollection(specs, mesh=mesh,
                                           grouped_a2a=grouped)
        mtables = mcoll.init(jax.random.key(0))
        ids_spec = P(None, "model") if n_shards > 1 else P()

        def run(k, mcoll=mcoll, mtables=mtables):
            @jax.jit
            def chain(tables, ids_stack):
                def body(carry, feats):
                    feats = {f: (v + carry.astype(jnp.int32)) % tv
                             for f, v in feats.items()}
                    vecs = mcoll.lookup(tables, feats, mode="alltoall")
                    tot = sum(jnp.abs(v).sum() for v in vecs.values())
                    return tot.astype(jnp.float32) % 1024, None

                final, _ = jax.lax.scan(body, jnp.float32(0), ids_stack)
                return final

            return lambda stack: chain(mtables, stack)

        def make_args(k, seed, ids_spec=ids_spec):
            r = np.random.default_rng(seed)
            stack = {
                f"ids{i}": jax.device_put(
                    r.integers(0, tv, (k, batch_size)).astype(np.int32),
                    NamedSharding(mesh, ids_spec))
                for i in range(n_tables)
            }
            float(sum(jnp.sum(v) for v in stack.values()))
            return (stack,)

        sec = chain_time(run, make_args, ks=(64, 512), reps=3)
        out[key] = round(sec * 1e6, 1)  # us
    out["n_shards"] = n_shards
    out["shape"] = f"B{batch_size}xV{vocab}xD{dim}"
    return out


def bench_big_table(vocab_tiny: int = 2_000_000, vocab_small: int = 50_000_000,
                    vocab_big: int = 400_000_000, dim: int = 8,
                    batch: int = 8192, kind: str = "rowwise_adagrad",
                    include_tiny: bool = True) -> dict:
    """O(batch)-traffic demonstration: the row-sparse step's latency must not
    scale with the table's vocab.  The headline pair runs fbgemm's huge-table
    configuration — EXACT_ROWWISE_ADAGRAD, one f32 accumulator per row — at
    4x10^8 rows x dim 8: table 12.8 GB + accumulator 1.6 GB ~ 14.4 GB, the
    largest adaptive-optimizer table one 16 GB v5e holds (Adam's two full
    moments cap out near 1.3x10^8 rows; see ``adam_100m`` in the output).

    ``big_over_small`` compares 50M -> 400M rows (8x) — both DRAM-resident,
    so the ratio isolates vocab scaling (measured 0.98-1.2 across runs;
    chain-differencing noise straddles 1.0).  The 2M ``tiny`` point is
    reported separately: a 64 MB table enjoys on-chip locality and makes a
    naive tiny-vs-big ratio (~1.9-2.4x) read as vocab scaling when it is a
    cache effect.  A dense optimizer sweep would be 8x slower at each step
    of this ladder; the sparse path touches O(batch) rows throughout."""
    import jax
    import jax.numpy as jnp

    from tdfo_tpu.ops.sparse import sparse_optimizer

    opt = sparse_optimizer(kind, lr=1e-3)
    out: dict[str, object] = {"vocab_tiny": vocab_tiny,
                              "vocab_small": vocab_small,
                              "vocab_big": vocab_big,
                              "dim": dim, "batch": batch, "optimizer": kind}
    points = [("small", vocab_small), ("big", vocab_big)]
    if include_tiny:
        points.insert(0, ("tiny", vocab_tiny))
    for label, vocab in points:
        # table + moments are created INSIDE the jitted chain: a per-chain
        # constant that the chain-length differencing cancels, and — unlike a
        # passed-in argument — XLA keeps exactly one copy (donating loop-carry
        # arguments would invalidate them between reps; a 100M-row table + f32
        # moments is ~9.6 GB, so an argument copy OOMs a 16 GB chip).  The
        # table starts ZEROED: random init pays an RNG temp the size of the
        # table (OOMs the 14.4 GB rowwise config), and row-RMW timing is
        # content-independent — each rep still runs unique work because the
        # ids/grads args are fresh.
        def run(k, vocab=vocab):
            @jax.jit
            def chain(key, ids_stack, grads_stack):
                del key
                table = jnp.zeros((vocab, dim), jnp.float32)
                slots = opt.init(table)

                def body(carry, xs):
                    t, s = carry
                    ids, g = xs
                    t, s = opt.update(t, s, ids, g)
                    return (t, s), None

                (t, s), _ = jax.lax.scan(body, (table, slots), (ids_stack, grads_stack))
                return t[0].sum()  # force dependency; O(D) fetch

            return lambda key, ids, grads: chain(key, ids, grads)

        def make_args(k, seed, vocab=vocab):
            r = np.random.default_rng(seed)
            ids = jax.device_put(r.integers(0, vocab, (k, batch)).astype(np.int32))
            grads = jax.device_put(r.standard_normal((k, batch, dim), np.float32))
            float(jnp.sum(ids) + jnp.sum(grads))
            return (jax.random.key(seed), ids, grads)

        # long chains: the per-step signal must clear the per-fetch noise
        sec = chain_time(run, make_args, ks=(32, 160), reps=3)
        out[f"step_ms_{label}"] = round(sec * 1e3, 4)
    if out["step_ms_small"] <= 0 or out["step_ms_big"] <= 0:
        # differencing lost to measurement noise; say so rather than report
        # a meaningless ratio
        out["invalid"] = True
        out["big_over_small"] = None
    else:
        out["big_over_small"] = round(out["step_ms_big"] / out["step_ms_small"], 3)
    return out


def _sim_cache_hit_rate(vocab: int, batch: int, cache_rows: int,
                        flush_every: int, steps: int = 192,
                        seed: int = 1234) -> tuple[float, int]:
    """Host-side replay of the update-cache directory policy (admit-all
    misses, retain the hottest C//2 by (freq desc, recency desc, id) at
    each flush, age retained frequencies //2 — ``ops/sparse.py``
    cache_flush) under the same zipf a=1.2 traffic the timed chains see.
    Returns ``(steady-state hit rate over the last half of the replay,
    peak directory occupancy)`` — the peak validates that ``cache_rows``
    really holds a flush interval's distinct ids (overflow means lost
    updates, which the trainer treats as a hard error)."""
    from tdfo_tpu.data.synthetic import zipf_ids

    r = np.random.default_rng(seed)
    keep_k = cache_rows // 2
    dir_ids = np.empty((0,), np.int64)
    freq: dict[int, int] = {}
    last: dict[int, int] = {}
    hits = total = peak = 0
    for step in range(steps):
        ids = zipf_ids(r, vocab, batch).astype(np.int64)
        u, cnt = np.unique(ids, return_counts=True)
        resident = np.isin(u, dir_ids)
        if step >= steps // 2:
            hits += int(cnt[resident].sum())
            total += batch
        dir_ids = np.union1d(dir_ids, u[~resident])
        for i in u.tolist():
            freq[i] = freq.get(i, 0) + 1
            last[i] = step
        peak = max(peak, len(dir_ids))
        if (step + 1) % flush_every == 0:
            retained = set(sorted(
                dir_ids.tolist(),
                key=lambda i: (-freq[i], -last[i], i))[:keep_k])
            # evicted entries lose their counters (re-admission resets
            # freq to 0, matching _cache_admit); retained ones age //2
            freq = {i: f // 2 for i, f in freq.items() if i in retained}
            last = {i: t for i, t in last.items() if i in retained}
            dir_ids = np.asarray(sorted(retained), np.int64)
    return hits / max(total, 1), peak


def bench_cache_zipf(vocab: int = 10_131_227, dim: int = 16,
                     batch: int = 8192, cache_rows: int = 131_072,
                     kind: str = "rowwise_adagrad",
                     flush_everies: tuple[int, ...] = (1, 8, 64),
                     ks: tuple[int, int] = (64, 192), reps: int = 3) -> dict:
    """Software MANAGED_CACHING amortization under power-law traffic: the
    cached step (directory route + cache-resident update; the big table is
    scattered into only on flush) vs the eager per-step dedupe + scatter,
    on the largest Criteo-Kaggle table (10.13M x 16, rowwise-adagrad) at
    zipf a=1.2 ids.  Emits the amortized ms/step at flush_every {1, 8, 64}
    — chain lengths are multiples of every interval, so each chain carries
    exactly k/flush_every coalesced flushes and the differencing amortizes
    them exactly — plus the host-simulated steady-state hit rate of the
    same retention policy.  flush_every=1 bounds the cache's overhead
    (route + admit + flush every step); the win case is 8/64 vs
    ``eager_ms``.  vs_eager > 1 = the cache wins."""
    import jax
    import jax.numpy as jnp

    from tdfo_tpu.data.synthetic import zipf_ids
    from tdfo_tpu.ops.sparse import sparse_optimizer

    opt = sparse_optimizer(kind, lr=1e-3)
    out: dict[str, object] = {"vocab": vocab, "dim": dim, "batch": batch,
                              "cache_rows": cache_rows, "optimizer": kind,
                              "zipf_a": 1.2}

    def make_args(k, seed):
        r = np.random.default_rng(seed)
        ids = jax.device_put(zipf_ids(r, vocab, (k, batch)))
        grads = jax.device_put(r.standard_normal((k, batch, dim), np.float32))
        float(jnp.sum(ids) + jnp.sum(grads))
        return (ids, grads)

    # eager baseline: the plain dedupe + XLA row-scatter step on the SAME
    # power-law traffic (uniform ids would overstate the cache's win)
    def run_eager(k):
        @jax.jit
        def chain(ids_stack, grads_stack):
            table = jnp.zeros((vocab, dim), jnp.float32)
            slots = opt.init(table)

            def body(carry, xs):
                t, s = carry
                ids, g = xs
                t, s = opt.update(t, s, ids, g)
                return (t, s), None

            (t, _), _ = jax.lax.scan(body, (table, slots),
                                     (ids_stack, grads_stack))
            return t[0].sum()

        return chain

    eager_sec = chain_time(run_eager, make_args, ks=ks, reps=reps)
    out["eager_ms"] = round(eager_sec * 1e3, 3)

    for fe in flush_everies:
        def run_cached(k, fe=fe):
            @jax.jit
            def chain(ids_stack, grads_stack):
                table = jnp.zeros((vocab, dim), jnp.float32)
                slots = opt.init(table)
                cache = opt.cache_init(table, cache_rows)

                def body(carry, xs):
                    t, s, c, step = carry
                    ids, g = xs
                    c, s = opt.cache_update(c, t, s, ids, g, step=step)

                    def flush(a):
                        c, t, s = a
                        c, t, s, _ = opt.cache_flush(c, t, s)
                        return c, t, s

                    c, t, s = jax.lax.cond((step + 1) % fe == 0, flush,
                                           lambda a: a, (c, t, s))
                    return (t, s, c, step + 1), None

                (t, _, c, _), _ = jax.lax.scan(
                    body, (table, slots, cache, jnp.int32(0)),
                    (ids_stack, grads_stack))
                # keep the table, the cache AND the overflow counter live
                return (t[0].sum() + c["rows"][0].sum()
                        + c["over"].astype(jnp.float32))

            return chain

        sec = chain_time(run_cached, make_args, ks=ks, reps=reps)
        hit, peak = _sim_cache_hit_rate(vocab, batch, cache_rows, fe)
        out[f"flush_every_{fe}"] = {
            "step_ms": round(sec * 1e3, 3),
            "hit_rate": round(hit, 4),
            "sim_peak_dir": peak,
            "would_overflow": peak > cache_rows,
            "vs_eager": round(eager_sec / max(sec, 1e-9), 3),  # >1 = cache wins
        }
    return out


def bench_cache_int8_zipf(vocab: int = 10_131_227, dim: int = 16,
                          batch: int = 8192, cache_rows: int = 131_072,
                          kind: str = "rowwise_adagrad",
                          flush_everies: tuple[int, ...] = (1, 64),
                          ks: tuple[int, int] = (64, 192),
                          reps: int = 3) -> dict:
    """:func:`bench_cache_zipf` on int8 STORAGE (the PR-18 composition the
    planner picks for Criteo under tight HBM): the table is 1-byte codes +
    the f32 [V, 2] (scale, offset) sidecar, cache rows mirror codes + grid,
    every cached write requantizes per row through ``quantize_rows`` with
    the eager path's SR key, and flush stays a bit-copy (codes scatter +
    one sidecar scatter).  The eager baseline is the plain-int8 dedupe +
    requantize-scatter step on the SAME power-law traffic.  vs_eager > 1 =
    the cache wins; non-flush steps never touch the [V, d] or [V, 2]
    arrays, so the win grows with flush_every exactly as in the f32
    record."""
    import jax
    import jax.numpy as jnp

    from tdfo_tpu.data.synthetic import zipf_ids
    from tdfo_tpu.ops.quant import sr_key as make_sr_key
    from tdfo_tpu.ops.sparse import sparse_optimizer

    opt = sparse_optimizer(kind, lr=1e-3)
    out: dict[str, object] = {"vocab": vocab, "dim": dim, "batch": batch,
                              "cache_rows": cache_rows, "optimizer": kind,
                              "table_dtype": "int8", "zipf_a": 1.2}

    def make_args(k, seed):
        r = np.random.default_rng(seed)
        ids = jax.device_put(zipf_ids(r, vocab, (k, batch)))
        grads = jax.device_put(r.standard_normal((k, batch, dim), np.float32))
        float(jnp.sum(ids) + jnp.sum(grads))
        return (ids, grads)

    def init_int8():
        codes = jnp.zeros((vocab, dim), jnp.int8)
        # unit grid: dequantize(0) == 0.0, matching the f32 record's zero
        # init; training writes re-grid touched rows per row as usual
        qs = jnp.tile(jnp.asarray([1.0, 0.0], jnp.float32), (vocab, 1))
        return codes, qs

    def run_eager(k):
        @jax.jit
        def chain(ids_stack, grads_stack):
            table, qs = init_int8()
            slots = opt.init(table)

            def body(carry, xs):
                t, s, q, step = carry
                ids, g = xs
                t, s, q = opt.update(
                    t, s, ids, g, qscale=q,
                    sr_key=make_sr_key(step, "bench_cache_int8"))
                return (t, s, q, step + 1), None

            (t, _, q, _), _ = jax.lax.scan(
                body, (table, slots, qs, jnp.int32(0)),
                (ids_stack, grads_stack))
            return (t[0].astype(jnp.float32) * q[0, 0] + q[0, 1]).sum()

        return chain

    eager_sec = chain_time(run_eager, make_args, ks=ks, reps=reps)
    out["eager_ms"] = round(eager_sec * 1e3, 3)

    for fe in flush_everies:
        def run_cached(k, fe=fe):
            @jax.jit
            def chain(ids_stack, grads_stack):
                table, qs = init_int8()
                slots = opt.init(table)
                cache = opt.cache_init(table, cache_rows)

                def body(carry, xs):
                    t, s, q, c, step = carry
                    ids, g = xs
                    c, s = opt.cache_update(
                        c, t, s, ids, g, step=step, qscale=q,
                        sr_key=make_sr_key(step, "bench_cache_int8"))

                    def flush(a):
                        c, t, s, q = a
                        c, t, s, q, _ = opt.cache_flush(c, t, s, q)
                        return c, t, s, q

                    c, t, s, q = jax.lax.cond(
                        (step + 1) % fe == 0, flush, lambda a: a,
                        (c, t, s, q))
                    return (t, s, q, c, step + 1), None

                (t, _, q, c, _), _ = jax.lax.scan(
                    body,
                    (table, slots, qs, cache, jnp.int32(0)),
                    (ids_stack, grads_stack))
                return ((t[0].astype(jnp.float32) * q[0, 0] + q[0, 1]).sum()
                        + c["rows"][0].astype(jnp.float32).sum()
                        + c["over"].astype(jnp.float32))

            return chain

        sec = chain_time(run_cached, make_args, ks=ks, reps=reps)
        hit, peak = _sim_cache_hit_rate(vocab, batch, cache_rows, fe)
        out[f"flush_every_{fe}"] = {
            "step_ms": round(sec * 1e3, 3),
            "hit_rate": round(hit, 4),
            "sim_peak_dir": peak,
            "would_overflow": peak > cache_rows,
            "vs_eager": round(eager_sec / max(sec, 1e-9), 3),  # >1 = cache wins
        }
    return out


def bench_quant_int8_fused(vocab: int = 2_000_000, dim: int = 64,
                           batch: int = 8192, kind: str = "adam",
                           ks: tuple[int, int] = (16, 64),
                           reps: int = 3) -> dict:
    """The other PR-18 composition: fused int8 byte-container fat lines
    (codes + bitcast (scale, offset) sidecar + f32 optimizer state in ONE
    line) vs the plain-int8 dedupe + requantize-scatter step, full update
    chain at the wide-row profile where the fat line wins on BOTH axes
    (d=64 adam: 640 B/row fused vs 1160 plain, one DMA stream vs three
    scatters + a sidecar scatter).  vs_plain > 1 = fused wins.  The two
    trajectories are bit-identical by construction (tests pin it); this
    record prices the layout choice the planner makes."""
    import jax
    import jax.numpy as jnp

    from tdfo_tpu.ops.pallas_kernels import fat_pack
    from tdfo_tpu.ops.quant import quantize_rows, sr_key as make_sr_key
    from tdfo_tpu.ops.sparse import sparse_optimizer
    from tdfo_tpu.plan.costs import table_hbm_bytes

    opt = sparse_optimizer(kind, lr=1e-2, small_vocab_threshold=0)
    out: dict[str, object] = {
        "vocab": vocab, "dim": dim, "batch": batch, "optimizer": kind,
        "hbm_bytes_fused": table_hbm_bytes(vocab, dim, optimizer=kind,
                                           dtype="int8", fused=True),
        "hbm_bytes_plain": table_hbm_bytes(vocab, dim, optimizer=kind,
                                           dtype="int8", fused=False),
    }

    def make_args(k, seed):
        r = np.random.default_rng(seed)
        ids = jax.device_put(r.integers(0, vocab, (k, batch)).astype(np.int32))
        grads = jax.device_put(
            r.standard_normal((k, batch, dim), np.float32))
        float(jnp.sum(ids) + jnp.sum(grads))
        return (jax.random.key(seed), ids, grads)

    def run_fused(k):
        @jax.jit
        def chain(key, ids_stack, grads_stack):
            fat = fat_pack(jax.random.uniform(key, (vocab, dim)),
                           dtype=jnp.int8, kind=kind)
            slots = opt.init(fat)

            def body(carry, xs):
                t, s, step = carry
                ids, g = xs
                t, s = opt.update(t, s, ids, g, embedding_dim=dim,
                                  sr_key=make_sr_key(step, "bench_qfused"))
                return (t, s, step + 1), None

            (t, _, _), _ = jax.lax.scan(body, (fat, slots, jnp.int32(0)),
                                        (ids_stack, grads_stack))
            return t[0, 0, :dim].astype(jnp.float32).sum()

        return chain

    def run_plain(k):
        @jax.jit
        def chain(key, ids_stack, grads_stack):
            codes, qs = quantize_rows(jax.random.uniform(key, (vocab, dim)))
            slots = opt.init(codes)

            def body(carry, xs):
                t, s, q, step = carry
                ids, g = xs
                t, s, q = opt.update(t, s, ids, g, qscale=q,
                                     sr_key=make_sr_key(step, "bench_qfused"))
                return (t, s, q, step + 1), None

            (t, _, q, _), _ = jax.lax.scan(
                body, (codes, slots, qs, jnp.int32(0)),
                (ids_stack, grads_stack))
            return (t[0].astype(jnp.float32) * q[0, 0] + q[0, 1]).sum()

        return chain

    fused_sec = chain_time(run_fused, make_args, ks=ks, reps=reps)
    plain_sec = chain_time(run_plain, make_args, ks=ks, reps=reps)
    out["fused_ms"] = round(fused_sec * 1e3, 3)
    out["plain_ms"] = round(plain_sec * 1e3, 3)
    out["vs_plain"] = round(plain_sec / max(fused_sec, 1e-9), 3)
    return out


def bench_serving(batch_size: int = 8192, embed_dim: int = 64,
                  top_k: int = 100) -> dict:
    """Serving-path latency: the frontend's jitted scoring program at its
    largest bucket and the exact-retrieval program, timed by the same
    chain differencing as the train benches (the inherited method, see the
    module docstring: constant per-call costs cancel in the K2-K1
    difference).

    ``serve_score8`` / ``serve_retrieve8``: per-batch latency at B=8192
    plus the derived throughput (scored rows/sec; retrieval queries/sec
    against the full 200k-item corpus at ``top_k``).  Both programs take
    tables/corpus as chain ARGUMENTS — never closures (compile payload).
    """
    import tempfile

    import jax
    import jax.numpy as jnp

    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh
    from tdfo_tpu.models.twotower import TwoTowerBackbone, ctr_embedding_specs
    from tdfo_tpu.ops.sparse import sparse_optimizer
    from tdfo_tpu.parallel.embedding import ShardedEmbeddingCollection
    from tdfo_tpu.serve.corpus import build_corpus, synthetic_item_features
    from tdfo_tpu.serve.export import export_bundle, load_bundle
    from tdfo_tpu.serve.retrieval import make_retrieval
    from tdfo_tpu.serve.scoring import make_scorer
    from tdfo_tpu.train.sparse_step import SparseTrainState

    import optax

    mesh = make_mesh(MeshSpec(data=-1, model=1, seq=1))
    coll = ShardedEmbeddingCollection(
        ctr_embedding_specs(SIZE_MAP, embed_dim, "row"), mesh=mesh)
    backbone = TwoTowerBackbone(embed_dim=embed_dim)
    dummy_e = {f: jnp.zeros((1, embed_dim), jnp.float32) for f in coll.features()}
    dummy_c = {"avg_rating": jnp.zeros((1,)), "num_pages": jnp.zeros((1,))}
    state = SparseTrainState.create(
        dense_params=backbone.init(jax.random.key(1), dummy_e, dummy_c)["params"],
        tx=optax.adamw(3e-4), tables=coll.init(jax.random.key(0)),
        sparse_opt=sparse_optimizer("adam", lr=3e-4),
    )
    with tempfile.TemporaryDirectory() as td:
        bundle = load_bundle(export_bundle(
            td + "/bundle", model="twotower", embed_dim=embed_dim,
            cat_columns=("user_id", "item_id", "language", "is_ebook",
                         "format", "publisher", "pub_decade"),
            cont_columns=("avg_rating", "num_pages"), size_map=SIZE_MAP,
            coll=coll, tables=state.tables, dense_params=state.dense_params))
    scorer = make_scorer(bundle, mesh=mesh)
    corpus_items = SIZE_MAP["item"]
    out: dict[str, object] = {"batch": batch_size, "top_k": top_k,
                              "corpus_items": corpus_items,
                              "embed_dim": embed_dim}

    # scoring chain: each scanned batch folds the carry into its ids so no
    # two scored batches are identical (defeats result caching)
    s_tables, s_dense = scorer._params

    def run_score(k):
        @jax.jit
        def chain(tables, dense, stack):
            def body(carry, batch):
                batch = dict(batch)
                batch["user_id"] = (batch["user_id"] + carry) % SIZE_MAP["user"]
                logits = scorer._score(batch, tables, dense)
                return jnp.abs(logits).sum().astype(jnp.int32) % 128, None

            final, _ = jax.lax.scan(body, jnp.int32(0), stack)
            return final

        return lambda stack: chain(s_tables, s_dense, stack)

    def make_score_args(k, seed):
        r = np.random.default_rng(seed)
        host = _make_host_batch(r, batch_size * k)
        host.pop("label")
        return (_stack_batches(mesh, host, k, batch_size),)

    sec = chain_time(run_score, make_score_args, ks=(16, 128), reps=3)
    out["serve_score8"] = {
        "batch_ms": round(sec * 1e3, 3),
        "rows_per_sec": round(batch_size / sec, 1),
    }

    corpus = build_corpus(
        scorer, synthetic_item_features(SIZE_MAP, corpus_items, seed=0),
        corpus_batch=8192, mesh=mesh)
    retrieve = make_retrieval(corpus, mesh=mesh, top_k=top_k)

    def run_retrieve(k):
        @jax.jit
        def chain(vectors, ids, qstack):
            def body(carry, q):
                s, _ = retrieve.jitted(q + carry, vectors, ids)
                return jnp.abs(s).sum() * jnp.float32(1e-9), None

            final, _ = jax.lax.scan(body, jnp.float32(0), qstack)
            return final

        return lambda qstack: chain(corpus.vectors, corpus.ids, qstack)

    def make_retrieve_args(k, seed):
        import jax

        r = np.random.default_rng(seed)
        q = jax.device_put(
            r.standard_normal((k, batch_size, embed_dim)).astype(np.float32))
        float(jnp.sum(q))
        return (q,)

    sec = chain_time(run_retrieve, make_retrieve_args, ks=(16, 128), reps=3)
    out["serve_retrieve8"] = {
        "batch_ms": round(sec * 1e3, 3),
        "queries_per_sec": round(batch_size / sec, 1),
    }
    return out


def bench_serve_seq(batch_size: int = 8192, n_items: int = 200_000,
                    max_len: int = 64, embed_dim: int = 64,
                    top_k: int = 100) -> dict:
    """``serve_seq8``: the SEQUENCE serving family's latency twins of
    ``serve_score8``/``serve_retrieve8`` — masked-position candidate
    scoring (history window in, appended-MASK logits over the 101-wide
    eval panel out) and next-item MIPS against the bias-folded output-head
    corpus (``serve/seq_scoring.py:item_corpus``, rows ``[W_out[:,v]; b_v]``
    so retrieval ranks exactly like the served logits).
    Timed by the same chain differencing as every other record (the
    inherited method); each scanned batch folds the carry into its history
    ids so no two scored batches are identical, and tables ride as chain
    ARGUMENTS, never closures (a closure is baked into the program)."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh
    from tdfo_tpu.data.seq_preprocessing import EVAL_NEG_NUM
    from tdfo_tpu.models.bert4rec import Bert4RecConfig, make_sharded_bert4rec
    from tdfo_tpu.serve.export import export_bundle, load_bundle
    from tdfo_tpu.serve.retrieval import make_retrieval
    from tdfo_tpu.serve.seq_scoring import item_corpus, make_seq_scorer

    mesh = make_mesh(MeshSpec(data=-1, model=1, seq=1))
    cfg = Bert4RecConfig(n_items=n_items, max_len=max_len,
                         embed_dim=embed_dim, n_heads=2, n_layers=2)
    coll, tables, backbone, dense = make_sharded_bert4rec(
        jax.random.key(0), cfg, mesh, sharding="row", fused_threshold=None)
    with tempfile.TemporaryDirectory() as td:
        bundle = load_bundle(export_bundle(
            td + "/bundle", model="bert4rec", embed_dim=embed_dim,
            cat_columns=(), cont_columns=(),
            size_map={"n_items": n_items}, coll=coll, tables=tables,
            dense_params=dense,
            seq={"max_len": max_len, "n_heads": cfg.n_heads,
                 "n_layers": cfg.n_layers}))
    scorer = make_seq_scorer(bundle, mesh=mesh)
    n_cands = EVAL_NEG_NUM + 1
    out: dict[str, object] = {"batch": batch_size, "n_items": n_items,
                              "max_len": max_len, "n_cands": n_cands,
                              "embed_dim": embed_dim, "top_k": top_k}
    s_tables, s_dense = scorer._params

    def _roll(batch, carry):
        # fresh valid item ids every scanned step; the window keeps its
        # appended-MASK last position so the scored program is the real one
        batch = dict(batch)
        seqs = (batch["seqs"] + carry) % n_items + 1
        batch["seqs"] = seqs.at[:, -1].set(scorer.mask_id)
        return batch

    def run_score(k):
        @jax.jit
        def chain(tables, dense, stack):
            def body(carry, batch):
                logits = scorer._score(_roll(batch, carry), tables, dense)
                return jnp.abs(logits).sum().astype(jnp.int32) % 128, None

            final, _ = jax.lax.scan(body, jnp.int32(0), stack)
            return final

        return lambda stack: chain(s_tables, s_dense, stack)

    def _make_host_panels(r, rows):
        return {
            "seqs": np.concatenate(
                [r.integers(1, n_items + 1, size=(rows, max_len - 1)),
                 np.full((rows, 1), n_items + 1)], axis=1).astype(np.int32),
            "cands": r.integers(1, n_items + 1,
                                size=(rows, n_cands)).astype(np.int32),
        }

    def make_score_args(k, seed):
        r = np.random.default_rng(seed)
        host = _make_host_panels(r, batch_size * k)
        return (_stack_batches(mesh, host, k, batch_size),)

    sec = chain_time(run_score, make_score_args, ks=(16, 128), reps=3)
    out["serve_seq_score8"] = {
        "batch_ms": round(sec * 1e3, 3),
        "rows_per_sec": round(batch_size / sec, 1),
    }

    # next-item retrieval: the output head IS the corpus (bias folded into
    # a d+1th column) — queries are [h, 1] last-position hidden states,
    # here synthesized at the right shape (query_embed cost is part of the
    # score record above)
    corpus = item_corpus(bundle, mesh=mesh)
    retrieve = make_retrieval(corpus, mesh=mesh, top_k=top_k)

    def run_retrieve(k):
        @jax.jit
        def chain(vectors, ids, qstack):
            def body(carry, q):
                s, _ = retrieve.jitted(q + carry, vectors, ids)
                return jnp.abs(s).sum() * jnp.float32(1e-9), None

            final, _ = jax.lax.scan(body, jnp.float32(0), qstack)
            return final

        return lambda qstack: chain(corpus.vectors, corpus.ids, qstack)

    def make_retrieve_args(k, seed):
        r = np.random.default_rng(seed)
        # query width d+1: [h, 1] against the bias-folded head corpus
        q = jax.device_put(
            r.standard_normal(
                (k, batch_size, embed_dim + 1)).astype(np.float32))
        float(jnp.sum(q))
        return (q,)

    sec = chain_time(run_retrieve, make_retrieve_args, ks=(16, 128), reps=3)
    out["serve_seq_retrieve8"] = {
        "batch_ms": round(sec * 1e3, 3),
        "queries_per_sec": round(batch_size / sec, 1),
    }
    return out


def bench_serve_fleet(replicas: int = 2, embed_dim: int = 16,
                      requests_per_step: int = 128, knee_steps: int = 3,
                      p99_slo_ms: float = 50.0) -> dict:
    """``serve_fleet8``: sustained QPS per replica at a fixed p99 SLO
    through the out-of-process serving stack (socket ingress -> replica
    processes, ``tdfo_tpu/serve/supervisor.py``).

    This measures the HOST serving stack — framing, balancing, process
    hops, micro-batching — not the chip: replica children always run
    ``JAX_PLATFORMS=cpu`` (a chip belongs to one process and the parent has
    it; the supervisor exports the variable into each child's environment),
    so the record is meaningful on and off TPU and carries no ``on_tpu``
    gate.  A closed-loop zipf sweep doubles concurrency per
    step; the knee is the last step whose p99 met the SLO.
    """
    import tempfile

    import jax

    from tdfo_tpu.core.config import Config, LoadgenSpec, ServingSpec
    from tdfo_tpu.models.twotower import TwoTowerBackbone, ctr_embedding_specs
    from tdfo_tpu.ops.sparse import sparse_optimizer
    from tdfo_tpu.parallel.embedding import ShardedEmbeddingCollection
    from tdfo_tpu.serve.export import export_bundle
    from tdfo_tpu.serve.loadgen import LoadGenerator
    from tdfo_tpu.serve.supervisor import ProcessFleet
    from tdfo_tpu.serve.swap import BundleStore
    from tdfo_tpu.train.sparse_step import SparseTrainState

    import jax.numpy as jnp
    import optax

    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh

    mesh = make_mesh(MeshSpec(data=-1, model=1, seq=1))
    coll = ShardedEmbeddingCollection(
        ctr_embedding_specs(SIZE_MAP, embed_dim, "row"), mesh=mesh)
    backbone = TwoTowerBackbone(embed_dim=embed_dim)
    dummy_e = {f: jnp.zeros((1, embed_dim), jnp.float32)
               for f in coll.features()}
    dummy_c = {"avg_rating": jnp.zeros((1,)), "num_pages": jnp.zeros((1,))}
    state = SparseTrainState.create(
        dense_params=backbone.init(jax.random.key(1), dummy_e,
                                   dummy_c)["params"],
        tx=optax.adamw(3e-4), tables=coll.init(jax.random.key(0)),
        sparse_opt=sparse_optimizer("adam", lr=3e-4),
    )
    vocab = {"user_id": SIZE_MAP["user"], "item_id": SIZE_MAP["item"],
             "language": SIZE_MAP["language"], "is_ebook": 2,
             "format": SIZE_MAP["format"],
             "publisher": SIZE_MAP["publisher"],
             "pub_decade": SIZE_MAP["pub_decade"]}
    with tempfile.TemporaryDirectory() as td:
        bundle_dir = export_bundle(
            td + "/bundle", model="twotower", embed_dim=embed_dim,
            cat_columns=tuple(vocab), cont_columns=("avg_rating",
                                                    "num_pages"),
            size_map=SIZE_MAP, coll=coll, tables=state.tables,
            dense_params=state.dense_params)
        store = BundleStore(td + "/store")
        if store.recover() is None:
            store.ingest_full(bundle_dir)
        cfg = Config().replace(
            serving=ServingSpec(replicas=replicas, fleet_mode="process"),
            loadgen=LoadgenSpec(mode="closed", requests=requests_per_step,
                                rows_per_request=16, p99_slo_ms=p99_slo_ms))
        fleet = ProcessFleet(store, cfg, workdir=td)
        try:
            fleet.sync()
            gen = LoadGenerator(fleet.ingress, cfg.loadgen, vocab,
                                ("avg_rating", "num_pages"))
            report = gen.knee(steps=knee_steps)
        finally:
            fleet.close()
    knee = report["knee"]
    out = {
        "replicas": replicas,
        "p99_slo_ms": p99_slo_ms,
        "steps": [{k: s[k] for k in ("concurrency", "achieved_qps",
                                     "p50_ms", "p99_ms", "shed", "failed",
                                     "slo_ok")}
                  for s in report["steps"]],
    }
    if knee is not None:
        out["knee_qps"] = round(knee["achieved_qps"], 1)
        out["qps_per_replica"] = round(knee["achieved_qps"] / replicas, 1)
        out["knee_p99_ms"] = knee["p99_ms"]
    return out


def bench_retrieval_scale(n_items_list=(1_000_000, 10_000_000),
                          dim: int = 64, batch: int = 256,
                          top_k: int = 100) -> dict:
    """``retrieve_twostage8``: exact f32 scan vs the int8 two-stage program
    (coarse ``4 * top_k`` over stored codes, exact re-rank of survivors) at
    corpus scales where the split starts to matter.  Synthetic corpora are
    drawn ON DEVICE (retrieval cost depends only on geometry, and a 10M x
    64 f32 host array is 2.5 GB of host->device copy for nothing); both
    programs take the corpus as chain ARGUMENTS, timed by the same chain
    differencing as every other record (the inherited method).  Recall@k of
    the two-stage answer is measured against the exact scan of the SAME
    int8 corpus — the exact program is the reference stand-in, verified
    against the argsort reference in tests/test_serve.py.  Not measured on
    the chip yet; docs/BUDGET.md "int8 corpora and two-stage retrieval"
    holds the prediction."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh
    from tdfo_tpu.ops.quant import quantize_rows
    from tdfo_tpu.serve.corpus import Corpus
    from tdfo_tpu.serve.retrieval import make_retrieval

    mesh = make_mesh(MeshSpec(data=-1, model=1, seq=1))
    n_shards = mesh.shape["data"]
    out: dict[str, object] = {"dim": dim, "batch": batch, "top_k": top_k}

    for n_items in n_items_list:
        n_pad = -(-n_items // n_shards) * n_shards
        sharding = NamedSharding(mesh, P("data", None))
        draw = jax.jit(
            lambda key: jax.random.normal(key, (n_pad, dim), jnp.float32),
            out_shardings=sharding)
        vectors = draw(jax.random.key(n_items))
        codes, qscale = jax.jit(quantize_rows, out_shardings=(
            sharding, sharding))(vectors)
        ids = jax.device_put(
            jnp.where(jnp.arange(n_pad) < n_items,
                      jnp.arange(n_pad, dtype=jnp.int32), -1),
            NamedSharding(mesh, P("data")))
        f32 = Corpus(vectors=vectors, ids=ids, n_items=n_items)
        i8 = Corpus(vectors=codes, ids=ids, n_items=n_items, qscale=qscale)
        exact = make_retrieval(f32, mesh=mesh, top_k=top_k)
        exact8 = make_retrieval(i8, mesh=mesh, top_k=top_k)
        two = make_retrieval(i8, mesh=mesh, top_k=top_k,
                             coarse_k=4 * top_k)

        def make_qargs(k, seed):
            r = np.random.default_rng(seed)
            q = jax.device_put(
                r.standard_normal((k, batch, dim)).astype(np.float32))
            float(jnp.sum(q))
            return (q,)

        def timed(jitted, operands):
            def run(k):
                @jax.jit
                def chain(qstack, *ops):
                    def body(carry, q):
                        s, _ = jitted(q + carry, *ops)
                        return jnp.abs(s).sum() * jnp.float32(1e-9), None

                    final, _ = jax.lax.scan(body, jnp.float32(0), qstack)
                    return final

                return lambda qstack: chain(qstack, *operands)

            return chain_time(run, make_qargs, ks=(8, 64), reps=3)

        sec_exact = timed(exact.jitted, (f32.vectors, f32.ids))
        sec_two = timed(two.jitted, (i8.vectors, i8.qscale, i8.ids))

        r = np.random.default_rng(1)
        q = jnp.asarray(r.standard_normal((batch, dim)), jnp.float32)
        _, i_ref = exact8(q)
        _, i_two = two(q)
        hits = sum(len(set(a.tolist()) & set(b.tolist()))
                   for a, b in zip(np.asarray(i_two), np.asarray(i_ref)))
        out[f"n{n_items // 1_000_000}m"] = {
            "exact_f32_ms": round(sec_exact * 1e3, 3),
            "twostage_int8_ms": round(sec_two * 1e3, 3),
            "speedup": round(sec_exact / sec_two, 2),
            "recall_at_k": round(hits / np.asarray(i_ref).size, 4),
            "corpus_bytes_f32": int(f32.vectors.nbytes),
            "corpus_bytes_int8": int(i8.vectors.nbytes + i8.qscale.nbytes),
        }
    return out


def bench_trace_overhead(run, make_args, ks=(5, 45), reps: int = 3) -> dict:
    """``trace_overhead``: the headline step chain re-timed with
    ``[telemetry] trace = true`` live (sinks in a throwaway dir) vs off.

    The step PROGRAM contains no trace calls — spans are host-side emits at
    serve/replay/cycle boundaries, and ``obs/trace.emit`` early-returns when
    unconfigured — so the on-vs-off delta is the claim itself: it must sit
    inside chain-differencing noise.  tests/test_trace.py pins the stronger
    static fact (trace on adds ZERO step-program equations, jaxpr
    byte-identity); this record is the measured companion.  Recipe and
    expected numbers: docs/BUDGET.md "trace overhead"."""
    import tempfile

    from tdfo_tpu.obs import trace as obs_trace

    sec_off = chain_time(run, make_args, ks=ks, reps=reps)
    with tempfile.TemporaryDirectory() as td:
        obs_trace.configure(td)
        try:
            sec_on = chain_time(run, make_args, ks=ks, reps=reps)
        finally:
            obs_trace.configure(None)
    return {
        "step_ms_trace_off": round(sec_off * 1e3, 3),
        "step_ms_trace_on": round(sec_on * 1e3, 3),
        "on_over_off": round(sec_on / sec_off, 4) if sec_off else None,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=8192)
    ap.add_argument("--embed-dim", type=int, default=64)
    ap.add_argument("--write-baseline", action="store_true",
                    help="record this run as BENCH_BASELINE.json")
    ap.add_argument("--skip-lookup-bench", action="store_true")
    ap.add_argument("--dense", action="store_true",
                    help="bench the dense regime (nn.Embed + dense AdamW) "
                         "instead of the sparse/DMP headline")
    ap.add_argument("--model", default="twotower",
                    choices=["twotower", "dlrm", "dlrm-criteo"],
                    help="CTR head for the sparse headline (dlrm-criteo = "
                         "the BASELINE.json north-star workload: 26 "
                         "Criteo-Kaggle tables, 33.76M rows, stacked, "
                         "rowwise-adagrad)")
    ap.add_argument("--table-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8"],
                    help="twotower/dlrm sparse headline only: embedding "
                         "STORAGE dtype (bfloat16 halves table HBM; int8 "
                         "quarters it plus an 8 B/row f32 (scale, offset) "
                         "sidecar — both keep compute f32 and write with "
                         "stochastic rounding)")
    ap.add_argument("--skip-big-table", action="store_true")
    ap.add_argument("--skip-serve-fleet", action="store_true",
                    help="skip the out-of-process fleet record "
                    "(serve_fleet8: ingress + replica processes on host "
                    "CPU — spawns subprocesses)")
    ap.add_argument("--skip-serving", action="store_true",
                    help="skip the serving-path records (serve_score8 / "
                         "serve_retrieve8)")
    ap.add_argument("--skip-serve-seq", action="store_true",
                    help="skip the sequence-serving records (serve_seq8: "
                         "masked-position scoring + next-item retrieval "
                         "against the item-table corpus)")
    ap.add_argument("--skip-cache", action="store_true",
                    help="skip the update-cache amortization record "
                         "(cache_zipf)")
    ap.add_argument("--skip-cache-int8", action="store_true",
                    help="skip the int8-storage update-cache record "
                         "(cache_int8_zipf)")
    ap.add_argument("--skip-quant-fused", action="store_true",
                    help="skip the fused-int8 fat-line vs plain-int8 "
                         "record (quant_int8_fused)")
    ap.add_argument("--skip-planner", action="store_true",
                    help="dlrm-criteo only: skip the planner-vs-defaults "
                         "record (planner_dlrm8)")
    ap.add_argument("--skip-trace-overhead", action="store_true",
                    help="skip the trace on-vs-off step-chain record "
                         "(trace_overhead) — re-times the headline chain "
                         "once more with span sinks live")
    ap.add_argument("--skip-retrieval-scale", action="store_true",
                    help="skip the 1M/10M-corpus exact-vs-two-stage record "
                         "(retrieve_twostage8) — the slowest serving record "
                         "(builds a 10M-row corpus on device)")
    ap.add_argument("--hot-vocab", type=int, default=0,
                    help="dlrm-criteo only: split every table's [0, K) "
                         "frequency-ranked prefix into a replicated hot head "
                         "(scatter-free one-hot MXU updates) and switch the "
                         "batches to power-law ids")
    ap.add_argument("--powerlaw", action="store_true",
                    help="dlrm-criteo only: power-law (zipf-ranked) ids "
                         "WITHOUT the hot/cold split — the ablation baseline "
                         "for --hot-vocab")
    args = ap.parse_args()
    if args.model == "dlrm-criteo" and args.embed_dim > 32:
        ap.error("dlrm-criteo: use --embed-dim 16 (the standard Kaggle-DLRM "
                 "dim; XLA lane-pads wider narrow tables past v5e HBM)")
    if args.dense and args.model != "twotower":
        # validate BEFORE measuring: a bad combination must not waste a run
        ap.error("--model is only valid for the sparse headline (drop --dense)")
    if (args.hot_vocab or args.powerlaw) and args.model != "dlrm-criteo":
        ap.error("--hot-vocab/--powerlaw require --model dlrm-criteo")
    if args.table_dtype != "float32" and (
            args.dense or args.model == "dlrm-criteo"):
        ap.error("--table-dtype applies to the twotower/dlrm sparse headline")

    from tdfo_tpu.core.mesh import configure_compile_cache

    configure_compile_cache()
    import jax

    hot_info = None
    table_bytes = None
    if args.dense:
        (run, make_args, global_batch, floor_bytes, flops_per_ex,
         counters_probe) = build_train_bench(args.batch_size, args.embed_dim)
    elif args.model == "dlrm-criteo":
        (run, make_args, global_batch, floor_bytes, flops_per_ex, hot_info,
         counters_probe) = (
            build_criteo_train_bench(args.batch_size, args.embed_dim,
                                     hot_vocab=args.hot_vocab,
                                     powerlaw=args.powerlaw)
        )
    else:
        (run, make_args, global_batch, floor_bytes, flops_per_ex, table_bytes,
         counters_probe) = (
            build_sparse_train_bench(args.batch_size, args.embed_dim,
                                     args.model, args.table_dtype)
        )
    sec_per_step = chain_time(run, make_args)
    if callable(floor_bytes):  # sparse floor depends on the generated batches
        floor_bytes = floor_bytes()

    peak_tflops, hbm_gbps, spec_assumed = chip_peaks()
    n_chips = jax.device_count()
    on_tpu = jax.devices()[0].platform == "tpu"

    # --- roofline sanity: refuse to report the impossible -----------------
    floor_sec = floor_bytes / (hbm_gbps * 1e9)
    if on_tpu and not spec_assumed and sec_per_step < floor_sec * 0.9:
        print(
            f"BENCH INVALID: measured {sec_per_step*1e3:.3f} ms/step beats the "
            f"HBM roofline floor {floor_sec*1e3:.3f} ms/step "
            f"({floor_bytes/1e6:.0f} MB optimizer traffic @ {hbm_gbps:.0f} GB/s). "
            "This is a caching/measurement artifact, not a real number.",
            file=sys.stderr,
        )
        sys.exit(1)

    examples_per_sec_per_chip = global_batch / sec_per_step / n_chips
    mfu = (flops_per_ex * global_batch / sec_per_step) / (n_chips * peak_tflops * 1e12)
    hbm_util = floor_bytes / sec_per_step / (hbm_gbps * 1e9)

    # one counters-on step AFTER the timed chains: the telemetry registry's
    # per-step numbers (touched/unique rows per table, grad/param norms) in
    # the record, from a separate program — the timed program stays
    # counters-off (byte-identity pinned by tests/test_telemetry.py)
    try:
        step_counters = counters_probe()
    except Exception as e:  # the probe must never kill the headline
        print(f"bench: counters probe failed: {e!r}", file=sys.stderr)
        step_counters = {}

    lookup = {} if args.skip_lookup_bench else bench_embedding_lookup()

    big_table = {}
    if on_tpu and not args.skip_big_table and not args.dense:
        try:
            big_table = bench_big_table()
            # the headline optimizer's own (smaller) scale pair rides along
            adam = bench_big_table(vocab_big=100_000_000, kind="adam",
                                   include_tiny=False)
            big_table["adam_100m"] = {
                k: adam[k] for k in ("vocab_big", "step_ms_small",
                                     "step_ms_big", "big_over_small")
            }
        except Exception as e:  # the demo must never kill the headline
            print(f"bench: big-table demo failed: {e!r}", file=sys.stderr)

    serving = {}
    if on_tpu and not args.skip_serving and not args.dense:
        try:
            serving = bench_serving(args.batch_size)
        except Exception as e:  # serving records must never kill the headline
            print(f"bench: serving bench failed: {e!r}", file=sys.stderr)

    serve_seq = {}
    if on_tpu and not args.skip_serve_seq and not args.dense:
        try:
            serve_seq = bench_serve_seq(args.batch_size)
        except Exception as e:  # seq records must never kill the headline
            print(f"bench: serve-seq bench failed: {e!r}", file=sys.stderr)

    serve_fleet = {}
    # no on_tpu gate: the fleet record measures the HOST serving stack
    # (replica children are always JAX_PLATFORMS=cpu)
    if not args.skip_serve_fleet and not args.dense:
        try:
            serve_fleet = bench_serve_fleet()
        except Exception as e:  # fleet record must never kill the headline
            print(f"bench: serve-fleet bench failed: {e!r}", file=sys.stderr)

    cache_zipf = {}
    if on_tpu and not args.skip_cache and not args.dense:
        try:
            cache_zipf = bench_cache_zipf()
        except Exception as e:  # cache record must never kill the headline
            print(f"bench: cache bench failed: {e!r}", file=sys.stderr)

    cache_int8_zipf = {}
    if on_tpu and not args.skip_cache_int8 and not args.dense:
        try:
            cache_int8_zipf = bench_cache_int8_zipf()
        except Exception as e:  # cache record must never kill the headline
            print(f"bench: int8-cache bench failed: {e!r}", file=sys.stderr)

    quant_int8_fused = {}
    if on_tpu and not args.skip_quant_fused and not args.dense:
        try:
            quant_int8_fused = bench_quant_int8_fused()
        except Exception as e:  # quant record must never kill the headline
            print(f"bench: fused-int8 bench failed: {e!r}", file=sys.stderr)

    retrieval_scale = {}
    if on_tpu and not args.skip_retrieval_scale and not args.dense:
        try:
            retrieval_scale = bench_retrieval_scale()
        except Exception as e:  # scale record must never kill the headline
            print(f"bench: retrieval-scale bench failed: {e!r}",
                  file=sys.stderr)

    trace_overhead = {}
    if on_tpu and not args.skip_trace_overhead:
        try:
            trace_overhead = bench_trace_overhead(run, make_args)
        except Exception as e:  # trace record must never kill the headline
            print(f"bench: trace-overhead bench failed: {e!r}",
                  file=sys.stderr)

    planner_rec = {}
    if args.model == "dlrm-criteo" and not args.skip_planner:
        # predictions are cheap host math and always emitted; the measured
        # arms only run on TPU under the DEFAULT (uniform-id) traffic the
        # planner's synthetic stats describe
        uniform = not args.hot_vocab and not args.powerlaw
        try:
            planner_rec = bench_planner_dlrm(
                args.batch_size, args.embed_dim,
                on_tpu=on_tpu and uniform,
                headline_step_ms=sec_per_step * 1e3 if uniform else None,
            )
        except Exception as e:  # planner record must never kill the headline
            print(f"bench: planner bench failed: {e!r}", file=sys.stderr)

    repo = Path(__file__).parent
    baseline_path = repo / "BENCH_BASELINE.json"
    model_name = "twotower" if args.dense else args.model
    bench_config = {"batch_size": args.batch_size, "embed_dim": args.embed_dim}
    if model_name != "twotower":
        # a different model family must never be compared against the
        # twotower baseline record (config equality gates vs_baseline)
        bench_config["model"] = model_name
    if args.hot_vocab or args.powerlaw:
        # hot/cold and power-law traffic change the workload: the config
        # keys gate vs_baseline so a skewed-traffic run never claims a
        # speedup over the uniform-traffic baseline record
        bench_config["hot_vocab"] = args.hot_vocab
        bench_config["powerlaw"] = True
    if args.table_dtype != "float32":
        # quantized storage changes the per-step byte budget: gate
        # vs_baseline so a bf16 run never claims a speedup over f32
        bench_config["table_dtype"] = args.table_dtype
    record = {
        "metric": f"{model_name.replace('-', '_')}_train_examples_per_sec_per_chip",
        "value": round(examples_per_sec_per_chip, 1),
        "unit": "examples/sec/chip",
        "regime": "dense_adamw" if args.dense else "dmp_sparse",
        "step_ms": round(sec_per_step * 1e3, 3),
        "roofline_floor_ms": round(floor_sec * 1e3, 3),
        # storage/traffic at the table STORAGE dtype: bf16 halves
        # table_bytes and the table share of bytes_per_step
        "table_bytes": table_bytes,
        "bytes_per_step": round(floor_bytes, 1),
        "hbm_utilization": round(hbm_util, 3),
        "mfu": round(mfu, 5),
        "counters": step_counters,
        "embedding_lookup_p50_us": lookup,
        "big_table_demo": big_table,
        "serving": serving,
        "serve_seq8": serve_seq,
        "serve_fleet8": serve_fleet,
        "cache_zipf": cache_zipf,
        "cache_int8_zipf": cache_int8_zipf,
        "quant_int8_fused": quant_int8_fused,
        "retrieve_twostage8": retrieval_scale,
        "planner_dlrm8": planner_rec,
        "trace_overhead": trace_overhead,
        "spec_assumed": spec_assumed,
        "device_kind": jax.devices()[0].device_kind,
        "config": bench_config,
    }
    if args.table_dtype in ("bfloat16", "int8"):
        # the quantized-storage record: same workload as the f32 headline,
        # half (bf16) / roughly a quarter (int8 codes + 8 B/row sidecar) the
        # table HBM — compare step_ms against the f32 run directly
        record[f"quant_{'bf16' if args.table_dtype == 'bfloat16' else 'int8'}"] = {
            "table_bytes": table_bytes,
            "bytes_per_step": round(floor_bytes, 1),
            "step_ms": round(sec_per_step * 1e3, 3),
        }
    if hot_info is not None and (hot_info["enabled"] or hot_info["powerlaw"]):
        record["hot_cold"] = {
            "enabled": hot_info["enabled"],
            "hot_vocab": hot_info["hot_vocab"],
            "powerlaw": hot_info["powerlaw"],
            "fully_hot_tables": hot_info["fully_hot_tables"],
            "hit_rate": (round(float(np.mean(hot_info["hit_rates"])), 4)
                         if hot_info["hit_rates"] else None),
            "step_ms": round(sec_per_step * 1e3, 3),
        }
    # only the DEFAULT headline config may claim the auto-written baseline
    # slot (a first-ever --model dlrm run must not disable twotower
    # regression tracking); explicit --write-baseline always wins
    default_cfg = model_name == "twotower" and not args.dense
    if on_tpu and (args.write_baseline
                   or (default_cfg and not baseline_path.exists())):
        baseline_path.write_text(json.dumps(record, indent=1) + "\n")

    vs_baseline = 1.0
    if baseline_path.exists():
        base = json.loads(baseline_path.read_text())
        comparable = (
            base.get("config") == record["config"]
            and base.get("device_kind") == record["device_kind"]
        )
        if comparable and base.get("value"):
            vs_baseline = round(examples_per_sec_per_chip / base["value"], 3)
            # same workload/metric, but say which regime produced the
            # baseline so a cross-regime speedup is legible as exactly that
            record["baseline_regime"] = base.get("regime", "dense_adamw")
        elif not comparable:
            print(
                f"bench: baseline config {base.get('config')}/{base.get('device_kind')} "
                f"!= run config {record['config']}/{record['device_kind']}; "
                "vs_baseline not comparable, reporting 1.0",
                file=sys.stderr,
            )

    print(json.dumps({**record, "vs_baseline": vs_baseline}))


if __name__ == "__main__":
    main()
