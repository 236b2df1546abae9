"""Config system: ``config.toml`` -> frozen :class:`Config` + ``size_map.json`` handshake.

TPU-native unification of the three per-backend loaders in the reference
(``jax-flax/utils.py:10-38``, ``tensorflow2/utils.py:10-48``,
``torchrec/utils.py:8-39``).  One dataclass covers both workload families
(TwoTower CTR and Bert4Rec sequential) plus the mesh/parallelism knobs that the
reference scattered across ``cluster.json``, torchx env vars, and strategy
factories.

The ``size_map.json`` file written by preprocessing is the contract between the
offline data layer and model construction (vocab sizes per categorical
feature), exactly as in the reference (``jax-flax/preprocessing.py:273-275`` ->
``jax-flax/utils.py:31-32``).
"""

from __future__ import annotations

import dataclasses
import json
import os

try:
    import tomllib
except ModuleNotFoundError:  # Python < 3.11 — tomli is the same parser/API
    import tomli as tomllib  # type: ignore[no-redef]
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Mapping

from tdfo_tpu.utils.faults import FaultSpec

__all__ = [
    "Config",
    "MeshSpec",
    "FaultSpec",
    "EmbeddingsSpec",
    "OnlineSpec",
    "ServingSpec",
    "TelemetrySpec",
    "TrainSpec",
    "read_configs",
    "load_size_map",
    "serving_model_kind",
]


def serving_model_kind(config) -> str:
    """Which serving family ``serve``/``online`` stand up for this config:
    ``"ctr"`` (twotower/dlrm scalar-logit bundles) or ``"seq"`` (bert4rec
    masked-position bundles).  ``[serving] model_kind = "auto"`` follows the
    model; an explicit kind was already cross-checked against the model at
    config time.  Unknown models refuse LOUDLY here — the serve/online
    dispatch point — instead of shape-crashing deep in a scorer."""
    kind = config.serving.model_kind
    if kind != "auto":
        return kind
    if config.model in ("twotower", "dlrm"):
        return "ctr"
    if config.model == "bert4rec":
        return "seq"
    raise ValueError(
        f"no serving family for model = {config.model!r}: CTR bundles "
        "serve twotower/dlrm, seq bundles serve bert4rec — serve/online "
        "cannot stand up this model")


@dataclass(frozen=True)
class MeshSpec:
    """Logical TPU mesh description.

    Replaces the reference's process-group / strategy / cluster.json plumbing
    (``torchrec/train.py:197-198``, ``tensorflow2/train_dp.py:21-36``,
    ``tensorflow2/train_ps.py:43-62``) with a single named-mesh spec.

    Axis sizes of ``-1`` mean "use all remaining devices" (at most one axis may
    be -1).  An axis of size 1 is kept in the mesh so sharding specs stay
    stable regardless of topology.
    """

    data: int = -1  # batch / data-parallel axis
    model: int = 1  # embedding-shard / tensor-parallel axis
    seq: int = 1  # sequence/context-parallel axis (ring attention)
    axis_names: tuple[str, ...] = ("data", "model", "seq")

    def sizes(self) -> tuple[int, ...]:
        return (self.data, self.model, self.seq)


@dataclass(frozen=True)
class EmbeddingsSpec:
    """``[embeddings]`` config table: frequency-partitioned hot/cold
    embedding storage (FAE / Neo-style popularity partitioning; torchrec
    ``MANAGED_CACHING`` analogue on a chip without SparseCore).

    ``hot_vocab`` > 0 enables the mode: preprocessing emits per-table
    hot-id sets (``hot_ids.json`` next to the parquet shards) of at most
    ``hot_vocab`` ids each, picked as the smallest frequency-ranked prefix
    covering ``hot_fraction`` of that column's lookup mass; at build time
    each table with a hot set splits into a small replicated hot table
    (scatter-free one-hot MXU update) and the residual cold table (the
    existing dedupe + row-scatter path over a smaller touched set).
    ``hot_vocab = 0`` disables the mode entirely (single-table storage,
    the default).
    """

    # per-table cap on the hot-id set size.  Keep <= ~16384: the one-hot
    # MXU segment-sum that makes hot updates scatter-free costs ~100-350 us
    # for vocabs 5k-16k on v5e and grows with the hot vocab.  0 disables.
    hot_vocab: int = 0
    # lookup-mass coverage target for the frequency pass: the hot set is
    # the smallest frequency-ranked id prefix whose train-split lookup
    # share reaches this fraction (then capped at hot_vocab).  Power-law id
    # traffic typically reaches 0.9 with a tiny prefix.
    hot_fraction: float = 0.9
    # grouped cross-table all-to-all (torchrec KJTAllToAll input-dist
    # parity): every row/table-sharded table's ids ride ONE offset-shifted
    # stream through ONE owner-sort + ONE id `all_to_all` (+ one for the
    # returned vectors), instead of a sort/bucket pipeline and 2 collectives
    # per table.  The backward takes the same single grouped id+grad
    # exchange.  Requires lookup_mode = "alltoall" + model_parallel; losses
    # are bit-identical to the per-table program.
    grouped_a2a: bool = False
    # STORAGE dtype of every embedding table in the DMP regime (fbgemm
    # quantized/mixed-precision TBE parity): "bfloat16" halves table HBM,
    # fat-line DMA bytes, and the grouped-a2a vector/grad payloads.  Compute
    # stays f32 — reads widen the small gathered block after the row gather,
    # writes requantize with stochastic rounding keyed on (step, table_id)
    # (ops/quant.py), so training stays bit-deterministic and
    # resume-identical.  "float32" (default) is byte-identical to the
    # unquantized storage layer.
    table_dtype: str = "float32"
    # STORAGE dtype of the Adam/Adagrad slot buffers of PLAIN (non-fused)
    # tables.  Fused fat-line tables pack their optimizer state into the
    # same lines as the rows, so their state width follows table_dtype.
    # rowwise_adagrad keeps its ONE f32 accumulator per row regardless
    # (fbgemm EXACT_ROWWISE_ADAGRAD parity contract) — bf16 slots with that
    # kind are rejected.
    slot_dtype: str = "float32"
    # per-table table_dtype overrides: a [embeddings.table_dtype_overrides]
    # toml sub-table mapping table name -> dtype string.  Tables not listed
    # use table_dtype.  Normalised to a sorted tuple of (name, dtype) pairs
    # so the Config stays hashable.
    table_dtype_overrides: Any = ()
    # device-resident update cache (fbgemm ``EmbeddingLocation.
    # MANAGED_CACHING`` / LXU-cache parity, software-managed): every plain
    # big-table array keeps a cache of this many rows resident in the train
    # state — sorted-id directory + value/slot mirrors + dirty mask +
    # frequency/recency counters.  Touched rows are admitted on miss
    # (gather-only) and updated scatter-free in the cache; dirty rows flush
    # back to the big table in ONE coalesced scatter every ``flush_every``
    # steps (and unconditionally before checkpoint/eval/export), amortizing
    # the ~60-110 ns/slot scatter floor across the interval.  Training is
    # bit-identical to the eager path.  Must bound the distinct rows an
    # array can touch per flush interval (the trainer fails loudly on
    # overflow).  0 disables (byte-identical default graphs).
    cache_rows: int = 0
    # cache write-back cadence in train steps: larger values amortize the
    # big-table scatter further but leave the main tables stale for longer
    # between flushes (training never reads stale values — the step serves
    # cached rows — but anything reading raw tables mid-interval would).
    # Checkpoint, eval, and serving export always flush first.
    flush_every: int = 64

    def __post_init__(self) -> None:
        ov = self.table_dtype_overrides
        if isinstance(ov, Mapping):
            ov = sorted(ov.items())
        object.__setattr__(
            self, "table_dtype_overrides",
            tuple((str(k), str(v)) for k, v in ov))

    def dtype_for(self, table_name: str) -> str:
        """Effective storage-dtype string for ``table_name``."""
        return dict(self.table_dtype_overrides).get(
            table_name, self.table_dtype)


@dataclass(frozen=True)
class ServingSpec:
    """``[serving]`` config table: online-inference knobs for the
    ``serve`` subcommand (``tdfo_tpu/serve/``) — checkpoint export,
    exact-MIPS candidate retrieval, and the micro-batching frontend.

    Every key is observable (``tests/test_config.py``): ``top_k`` is the
    retrieval output width, ``corpus_batch`` the item-tower sweep chunk,
    ``max_batch``/``batch_deadline_ms``/``buckets`` drive micro-batch
    assembly and the padded-shape set the jit cache may hold, and
    ``max_queue``/``shed_policy``/``swap_poll_s``/``max_bad_deltas`` are the
    overload/hot-swap resilience knobs (``serve/frontend.py`` admission
    control, ``serve/swap.py`` delta polling + quarantine).
    """

    # retrieved candidates per query (``lax.top_k`` width; ~16 us for an
    # 8k argsort on v5e, so exact brute-force MIPS needs no ANN index at
    # Goodreads/Criteo corpus scales)
    top_k: int = 100
    # item-tower sweep chunk when materialising the [N_items, D] corpus —
    # one jitted program, N/corpus_batch dispatches
    corpus_batch: int = 8192
    # micro-batcher flush threshold: a batch ships as soon as it holds
    # this many rows (must fit the largest bucket)
    max_batch: int = 8192
    # oldest-request deadline in milliseconds: when it expires the batcher
    # ships a PARTIAL padded batch instead of stalling the queue (graceful
    # degradation; 0 ships every request as its own batch)
    batch_deadline_ms: float = 10.0
    # allowed padded batch shapes (ascending).  Requests pad up to the
    # smallest bucket that fits, so the serving jit cache holds at most
    # ``len(buckets)`` programs — the compile-count regression contract.
    buckets: tuple[int, ...] = (256, 1024, 8192)
    # admission-queue cap in pending REQUESTS; an arrival beyond it sheds
    # deadline-expired requests first, then applies shed_policy (0 = the
    # pre-resilience unbounded queue)
    max_queue: int = 0
    # who loses when the bounded queue is still full after deadline sweeps:
    # "oldest" displaces the longest-waiting request (its latency bound is
    # nearest to broken anyway), "reject" bounces the new arrival
    shed_policy: str = "oldest"
    # how often the serving loop checks the export chain for the successor
    # delta bundle (serve/swap.py DeltaPoller cadence).  0 polls every tick;
    # a backwards host-clock jump re-arms rather than stalling (the poller
    # runs on an injectable monotonic-ish clock — see tests).
    swap_poll_s: float = 1.0
    # consecutive quarantined (digest-corrupt) deltas before the frontend
    # flips the degraded flag into its heartbeat — still serving the last
    # good version, but loudly
    max_bad_deltas: int = 3
    # two-stage retrieval (ScaNN, Guo et al. 2020 — quantized coarse scan
    # then exact re-rank): candidates kept per query by the coarse stage
    # before the exact f32 re-rank narrows them to top_k.  0 (default)
    # keeps the single-stage exact scan — byte-identical serving graphs.
    # Must be >= top_k when set; values above the corpus size degenerate
    # statically to the exact scan (bitwise-equal results).
    coarse_k: int = 0
    # storage dtype of the coarse-stage corpus scan: "int8" (rowwise
    # (scale, offset) codes, 4x less corpus HBM than f32), "bfloat16"
    # (2x), or "float32" (candidate pruning without quantization).  The
    # re-rank always gathers the exact f32 vectors.
    coarse_dtype: str = "int8"
    # log full feature payloads (+ labels when present) into the request
    # JSONL so served traffic can replay as an incremental training stream
    # (data/replay.py; Monolith §3.3 online-training joiner analogue).
    # Default-off: feature payloads multiply the log's byte rate.
    log_features: bool = False
    # rotate the request log into a sealed, digest-stamped segment once the
    # active file reaches this many bytes (0 = one unbounded segment).
    # Replay tails sealed segments with end-to-end verification; rotation
    # is atomic (seal lands before the successor opens).
    log_segment_bytes: int = 0
    # frontend replica count (serve/fleet.py): N micro-batching frontends
    # share one BundleStore and follow its CURRENT/CANARY pointers; each
    # writes its own request-log directory (replica-<k>) that the online
    # supervisor folds back into one exactly-once stream.  1 = the
    # single-frontend layout of PRs 9-10, byte-identical code path.
    replicas: int = 1
    # bundle-store retention: keep at most this many newest published
    # version directories beyond the protected CURRENT/CANARY chain
    # (serve/swap.py gc_versions, wired through recover() and promotion).
    # 0 = keep everything (the pre-retention behaviour).
    keep_versions: int = 0
    # fleet execution boundary: "inproc" keeps replicas as Python objects
    # inside the supervisor process (the PR-14 layout — spoofed-mesh unit
    # tests, zero process overhead); "process" runs each ReplicaFrontend as
    # a real OS process behind the socket ingress (serve/supervisor.py +
    # serve/ingress.py + serve/wire.py) so death drills are real SIGKILLs
    # and respawns cross a true process boundary.  Requires replicas >= 2.
    fleet_mode: str = "inproc"
    # heartbeat-staleness eviction window in milliseconds: the balancer
    # treats a replica whose last heartbeat is older than this as dead and
    # stops routing requests to it (serve/ingress.py; a stalled replica
    # keeping its final queue_depth forever was the PR-14 gap).  Must be
    # > 0 — a fleet cannot run without an eviction bound.
    heartbeat_stale_ms: float = 5000.0
    # wire-protocol frame cap in bytes (serve/wire.py): a declared frame
    # length beyond this is refused BEFORE the body is read, on both sides
    # — the bound on memory a malformed or hostile peer can demand.
    max_frame_bytes: int = 8 << 20
    # ingress -> replica connect retries (serve/wire.py connect, routed
    # through utils/retry.backoff_delay — the single backoff law); the
    # respawn window is exactly when these fire.  The default schedule
    # (10 attempts from 10 ms, capped at 2 s, ~4.5 s of cumulative sleep)
    # rides out a fresh child's interpreter + jax import; the child binds
    # its listener before loading the bundle, so the first RPC blocks on
    # the slow part instead of the connect.
    connect_retries: int = 10
    # base delay in milliseconds for the connect-retry backoff schedule
    # (doubles per attempt, capped + jittered by utils/retry.backoff_delay).
    connect_base_ms: float = 10.0
    # supervisor respawn backoff base in milliseconds: a replica's K-th
    # consecutive death waits backoff_delay(K) scaled from this base before
    # the respawn (serve/supervisor.py), so a crash-looping child cannot
    # hot-spin the supervisor.
    respawn_base_ms: float = 50.0
    # cap on the respawn backoff delay in milliseconds.
    respawn_max_ms: float = 2000.0
    # flap-quarantine window in seconds: deaths older than this no longer
    # count against a replica.
    flap_window_s: float = 30.0
    # deaths within flap_window_s that quarantine a replica permanently
    # (no further respawns; the fleet degrades to the survivors and the
    # quarantine is recorded loudly, never silent).
    flap_max_deaths: int = 3
    # which bundle family `serve`/`online` stand up: "auto" follows the
    # config's model (twotower/dlrm -> ctr, bert4rec -> seq), "ctr"/"seq"
    # pin it explicitly and REFUSE a mismatched model at config time — the
    # loud dispatch error instead of a shape crash deep in the scorer.
    model_kind: str = "auto"
    # newest raw-history items the seq frontend keeps when windowing a
    # ragged user history into the fixed [max_len] eval window (truncate-
    # left, torchrec/preprocessing.py:229-239).  0 = max_len - 1 (the eval
    # protocol's full window); smaller values drop older items and left-pad
    # more.  Must leave room for the appended MASK: <= max_len - 1.
    max_history: int = 0
    # row-count bucket set for the SEQ frontend's micro-batcher (sequence
    # requests carry [n, max_len] history panels, so the right fill
    # thresholds are smaller than CTR's).  Empty = reuse `buckets`.  The
    # jit-cache bound is len(history_buckets) programs, same contract.
    history_buckets: tuple[int, ...] = ()


@dataclass(frozen=True)
class LoadgenSpec:
    """``[loadgen]`` config table: the closed/open-loop load-generation
    harness (``serve/loadgen.py`` + ``launch.py loadgen``) that drives a
    process fleet to saturation and records the latency/throughput knee
    through the trace assembler's cohort p50/p99 histograms.

    Every key is observable (``tests/test_config.py``).
    """

    # arrival discipline: "closed" keeps exactly `concurrency` requests in
    # flight (each completion immediately issues the next — the classic
    # closed-loop saturation probe); "open" issues at `rate_qps` regardless
    # of completions (the knee appears as queueing + sheds, not slowdown).
    mode: str = "closed"
    # total requests to issue per run.
    requests: int = 200
    # closed-loop concurrency: in-flight request cap (ignored for "open").
    concurrency: int = 8
    # open-loop arrival rate in requests/second (ignored for "closed").
    rate_qps: float = 100.0
    # zipf exponent for item-popularity skew in generated request batches
    # (> 1; larger = hotter head — the realistic serving distribution).
    zipf_a: float = 1.1
    # rows per generated request batch (micro-batcher fill pressure).
    rows_per_request: int = 4
    # rng seed for the request stream (ids, continuous features, arrival
    # jitter) — a fixed seed makes knee runs comparable across builds.
    seed: int = 606
    # the SLO the knee is measured against: serve/loadgen.py `knee()`
    # reports sustained QPS/replica at this p99 bound, and past the knee
    # admitted requests must still meet it while sheds are counted, never
    # silent.
    p99_slo_ms: float = 50.0


@dataclass(frozen=True)
class TrainSpec:
    """``[train]`` config table: train-loop pipelining knobs
    (torchrec ``TrainPipelineSparseDist`` parity)."""

    # cross-batch input-dist pipelining: batch N+1's owner-bucketing + id
    # all-to-all (which never reads the tables) is issued inside the jitted
    # step BEFORE batch N's dense fwd/bwd + table update, so XLA's
    # latency-hiding scheduler overlaps the ICI exchange with MXU work
    # (torchrec/train.py TrainPipelineSparseDist).  Losses are bit-identical
    # to eager order but arrive one batch late; the trainer primes on the
    # first batch and flushes the last at epoch end.  Requires
    # grouped_a2a = true and steps_per_execution = 1.
    pipeline_overlap: bool = False


@dataclass(frozen=True)
class LmSpec:
    """``[lm]`` config table: the architecture and the share of a causal
    decoder (``model`` names the family: ``"olmo_hybrid"``,
    ``tdfo_tpu/models/olmo_hybrid.py``; ``"nemotron_h"``,
    ``tdfo_tpu/models/nemotron_h.py``).  Key names are the published
    ``config.json``'s (huggingface ``model_type`` of the same name) where it
    has one; a head, group or expert count there is the PUBLISHED count (it
    sets the head and group sizes and the router's width), and the
    ``*_held`` keys say how many of them this process group's layers hold
    (the chip's share of a deployment that divides each layer by heads and
    experts; 0 = all of them).  A family reads the keys its module's
    ``LmConfig`` has as fields; a key of another family must stay at its
    default (``Trainer`` refuses it at build)."""

    vocab_size: int = 0            # rows of the token table and of the head
    hidden_size: int = 0
    # published query heads of the attention layers (olmo_hybrid: of both
    # kinds of layer, and head size = hidden / this)
    num_attention_heads: int = 0
    rms_norm_eps: float = 1e-6     # nemotron_h: the config's layer_norm_epsilon
    # ---- olmo_hybrid
    intermediate_size: int = 0     # SwiGLU width
    # one entry a layer: "linear_attention" (Gated DeltaNet) | "full_attention"
    layer_types: tuple[str, ...] = ()
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    # beta = 2 * sigmoid(.) (the state's eigenvalues may go negative)
    linear_allow_neg_eigval: bool = True
    full_heads_held: int = 0
    linear_heads_held: int = 0
    # ---- nemotron_h
    # one letter a layer: "M" Mamba-2 | "*" attention | "E" experts
    hybrid_override_pattern: str = ""
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    n_groups: int = 0              # groups of Mamba heads sharing B and C
    conv_kernel: int = 4
    num_key_value_heads: int = 0
    head_dim: int = 0
    n_routed_experts: int = 0      # the router's width
    num_experts_per_tok: int = 0
    moe_latent_size: int = 0
    moe_intermediate_size: int = 0
    moe_shared_expert_intermediate_size: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    mamba_heads_held: int = 0      # whole groups of them
    attention_heads_held: int = 0  # with the key/value heads they read
    experts_held: int = 0
    first_expert_held: int = 0     # index of the first held expert


# the causal decoder families (``tdfo_tpu/models/<name>.py``) and the [lm]
# keys each needs > 0.  That is all this module, which does not import jax,
# says of a family: which keys it reads and what they must satisfy together
# is its module's ``LmConfig``, which ``Trainer`` constructs at build
LM_FAMILIES: dict[str, tuple[str, ...]] = {
    "olmo_hybrid": (
        "vocab_size", "hidden_size", "num_attention_heads",
        "intermediate_size", "linear_key_head_dim", "linear_value_head_dim"),
    "nemotron_h": (
        "vocab_size", "hidden_size", "num_attention_heads", "mamba_num_heads",
        "mamba_head_dim", "ssm_state_size", "n_groups", "num_key_value_heads",
        "head_dim", "n_routed_experts", "num_experts_per_tok",
        "moe_latent_size", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size"),
}


@dataclass(frozen=True)
class TelemetrySpec:
    """``[telemetry]`` config table: flight-recorder knobs (``tdfo_tpu/obs``).

    The reference's only observability is tqdm bars and a
    ``tf.keras.callbacks.TensorBoard`` callback (``tensorflow2/
    train_ps.py:154``); torchrec's production analogue is ``TrainPipeline``
    throughput logging.  Every key is observable
    (``tests/test_telemetry.py``).
    """

    # in-graph step diagnostics (per-table touched/unique rows, cache
    # hit/miss/dirty/flushed, a2a fill/overflow, grad/param norms,
    # nonfinite logits) carried alongside the pending losses — zero extra
    # host syncs, fetched at log cadence into metrics.jsonl (+ TB when
    # tensorboard = true).  false compiles a byte-identical step jaxpr
    # (pinned by test) so the default path cannot regress.
    counters: bool = False
    # compile/retrace + memory events: every jax compilation (name,
    # duration, per-name count) appends to <log_dir>/events.jsonl;
    # compilations after warmup are flagged as unexpected retraces with a
    # loud warning, and device.memory_stats() live/peak bytes are sampled
    # at log cadence with a run-peak watermark in the final summary
    # (no-op on backends without memory_stats, e.g. spoofed CPU devices).
    events: bool = False
    # stall watchdog: a daemon thread appends {last_step, step_age_s} to
    # <log_dir>/heartbeat.jsonl and logs a LOUD warning with every
    # thread's Python stack when no train step completes within this many
    # seconds (a hung device or collective, made diagnosable).
    # 0 disables the watchdog thread (heartbeat.jsonl is not written).
    stall_timeout_s: float = 0.0
    # size-based rotation for the run's append-only JSONL sinks
    # (metrics.jsonl via MetricLogger, retries.jsonl via utils/retry,
    # events.jsonl, heartbeat*.jsonl, and the trace-*.jsonl span sinks):
    # when a sink crosses this many bytes it is atomically renamed to
    # `<name>.1` (replacing any previous overflow) and a fresh file
    # continues — a long-running online loop must not fill the disk.
    # 0 = unbounded.
    log_rotate_bytes: int = 0
    # span-based causal tracing (tdfo_tpu/obs/trace.py): every component of
    # the online loop appends correlation-id-carrying spans to per-component
    # trace-*.jsonl sinks under <out_dir>/trace, assembled offline by
    # `launch.py obs` into per-cycle causal timelines, freshness lag, and
    # fleet latency percentiles.  Spans are host-side only: false (the
    # default) emits nothing and the step program is byte-identical either
    # way (pinned by tests/test_trace.py).
    trace: bool = False


@dataclass(frozen=True)
class OnlineSpec:
    """``[online]`` config table: the serve -> retrain -> delta-export ->
    swap supervisor (``tdfo_tpu/train/online.py``; Monolith §3.3 online
    training / torchrec streaming-retrain analogue).

    The supervisor tails the frontend's request log through the crash-safe
    replay consumer (``data/replay.py``), trains ``steps_per_cycle``
    incremental steps, checkpoints state + replay cursor atomically, then
    ``export_delta`` -> ``BundleStore`` publish -> ``MicroBatcher.swap`` —
    forever (or ``max_cycles``).  Every knob below is observable
    (``tests/test_online.py`` / ``tests/test_replay.py``).
    """

    # directory of request-log segments to tail ("" disables the online
    # loop; `launch online` requires it).  The frontend writes it when
    # [serving] log_features is on.
    request_log: str = ""
    # incremental train steps (= replay batches) per cycle before the
    # delta-export/publish/swap stages run.  Each step consumes one
    # per_device_train_batch_size * data-axis batch from the log.
    steps_per_cycle: int = 8
    # stop after this many full cycles (0 = run until the log is exhausted
    # — the test/drain mode; production tails forever).
    max_cycles: int = 0
    # complete-but-garbage log records tolerated (quarantined with a
    # counter) before replay fails the run — mirrors max_bad_shards.
    # 0 = any bad record is fatal.
    max_bad_records: int = 0
    # bounded-lag backpressure: when replay falls more than this many
    # records behind the durable log head, lag_policy decides (0 = lag is
    # unbounded, the metric still reports).
    max_lag_records: int = 0
    # "fail" refuses to train on stale data (raises once max_lag_records is
    # exceeded); "skip" drops oldest records down to the bound — counted in
    # replay/skipped — and keeps training on fresh traffic.
    lag_policy: str = "fail"
    # canary gatekeeper (Monolith §3.3 staged parameter sync): when > 0,
    # every candidate bundle is shadow-scored before publish, published to
    # the CANARY pointer (served by canary_fraction of the fleet), watched
    # for this many heartbeat rounds, then promoted to CURRENT or rolled
    # back to the last good version bitwise.  0 = the ungated PR-10 path
    # (publish straight to CURRENT).  Requires [serving] replicas >= 2.
    canary_cycles: int = 0
    # fraction of replicas that serve the CANARY pointer during the watch
    # window (at least one replica; always fewer than the whole fleet, so
    # a regression reaches at most this slice of traffic).
    canary_fraction: float = 0.25
    # maximum tolerated AUC drop: the shadow gate refuses a candidate whose
    # held-out AUC falls more than this below the serving baseline, and the
    # canary watch rolls back when canary-replica AUC falls more than this
    # below the stable replicas.
    max_auc_regression: float = 0.02
    # latency verdict term for the canary watch: roll the candidate back
    # when the canary cohort's heartbeat-scoring p99 exceeds the stable
    # cohort's p99 by more than this many milliseconds across the watch
    # window (nearest-rank percentile, obs/aggregate.percentile — the same
    # statistic `launch.py obs` reports offline).  Catches regressions AUC
    # cannot see (a slow scorer serves stale ranking under load).  0
    # disables the term; requires canary_cycles > 0 to mean anything.
    max_p99_regression_ms: float = 0.0
    # replay batches held out per gated cycle as the shadow-eval slice:
    # traffic the candidate has NOT trained on (it trains in a later cycle
    # — progressive validation), scored by candidate + baseline for the
    # gate and by every replica for canary heartbeats.
    shadow_eval_batches: int = 1
    # replay-log retention: keep at most this many fully-consumed sealed
    # segments behind the committed cursor, deleting older ones (GC refuses
    # to touch any segment the cursor has not fully passed).  0 = keep
    # everything.  NOTE: after GC the log only replays from a committed
    # cursor — replay-from-zero is gone by design.
    keep_consumed_segments: int = 0


@dataclass(frozen=True)
class PlannerSpec:
    """``[planner]`` config table: cost-model-driven auto-sharding
    (``tdfo_tpu/plan``; torchrec ``EmbeddingShardingPlanner`` parity).

    ``python -m tdfo_tpu.launch plan --config ...`` prices every per-table
    placement against the measured v5e cost table (``plan/costs.py``) using
    the preprocessing traffic stats (``table_stats.json``) and writes a
    deterministic ``sharding_plan.json``; setting ``plan`` to that path
    makes the trainer apply it as per-table spec overrides (sharding /
    fused storage / dtype / hot split) and stamp its digest into
    checkpoints.
    """

    # path to a sharding_plan.json consumed at train time ("" = no plan;
    # the hand-set global knobs apply).  A plan OWNS the per-table levers,
    # so it conflicts with hot_vocab / cache_rows / non-f32 dtypes
    # (validated below) — those must come from the plan, not the config.
    plan: str = ""
    # per-device HBM budget the PLANNING step must fit allocated table +
    # optimizer-slot bytes under (128-lane padding included); 0 = unlimited.
    hbm_gb: float = 0.0
    # device count the plan targets (row shards divide descriptor work and
    # bytes by this; table-wise placement balances across it).
    n_devices: int = 1


@dataclass(frozen=True)
class Config:
    """Unified training configuration.

    Field-by-field parity sources:
      * data/paths + streaming: ``jax-flax/config.toml``, ``jax-flax/utils.py:10-33``
      * write_format / steps_per_execution / jit_xla / use_tpu:
        ``tensorflow2/utils.py:10-38`` (jit_xla=false here means eager debug
        execution — a REAL knob, unlike the reference's normalise-to-None)
      * sequence-model params (n_heads..mask_prob, model_parallel):
        ``torchrec/utils.py:8-34`` (incl. the ``max_len >= sliding_step`` assert)
    """

    # --- data (L1) ---
    data_dir: Path = Path("data/goodreads")
    train_data: str = "train_part_*.parquet"
    eval_data: str = "eval_part_*.parquet"
    # held-out TEST split (bert4rec leave-last-one): evaluated ONCE after
    # fit() finishes.  The reference computes this split and never consumes
    # it (torchrec/train.py:147-177); empty string disables.
    test_data: str = "test_part_*.parquet"
    streaming: bool = True
    write_format: str = "parquet"
    num_workers: int = 0
    shuffle_buffer_size: int = 2_000_000

    # --- optimisation (L4) ---
    n_epochs: int = 10
    learning_rate: float = 3e-4
    weight_decay: float = 1e-4
    per_device_train_batch_size: int = 2048
    per_device_eval_batch_size: int = 2048
    mixed_precision: bool = False
    loss_scale: str = "dynamic"  # "dynamic" | "none" (only used with f16)
    seed: int = 42

    # --- model (L2) ---
    # "twotower" | "bert4rec" | "dlrm" | a causal decoder: "olmo_hybrid" |
    # "nemotron_h"
    model: str = "twotower"
    embed_dim: int = 16
    # custom CTR feature schema (dlrm only): categorical column names (one
    # embedding table each, vocab sizes from size_map) and continuous column
    # names for the bottom MLP.  Empty = the Goodreads TwoTower schema.
    # This is what trains Criteo-class data (data/criteo_preprocessing.py,
    # BASELINE.json north-star family): 26 cats + 13 conts by column name.
    categorical_features: tuple[str, ...] = ()
    continuous_features: tuple[str, ...] = ()
    # sequential-model params (Bert4Rec)
    n_heads: int = 2
    n_layers: int = 2
    max_len: int = 20
    sliding_step: int = 10
    mask_prob: float = 0.2
    dropout: float = 0.1
    # runtime variable-length sequences (torchrec KJT parity): preprocessing
    # writes RAGGED windows (preprocess-seq pads nothing), the loader ships
    # (values, lengths) pairs, and jagged_to_dense runs inside the jitted
    # step.  bert4rec only.
    jagged: bool = False

    # --- parallelism (L3) ---
    model_parallel: bool = False
    embedding_sharding: str = "row"  # "row" | "column" | "table" | "replicated"
    # embedding-lookup program (parallel/embedding.py): "gspmd" (compiler
    # schedules the collectives), "psum" (explicit shard_map, one psum), or
    # "alltoall" (torchrec input-dist/output-dist parity, 2 collectives)
    lookup_mode: str = "gspmd"
    # alltoall send-bucket capacity as a multiple of the balanced share
    # (local_batch / n_shards); 0 = exact worst case (capacity = local
    # batch).  Finite factors shrink the a2a payload ~n_shards/factor but
    # DROP ids past a bucket's capacity under extreme skew — they resolve
    # to zero vectors, a silent quality hazard.  The Trainer therefore logs
    # `a2a_overflow_ids` (dropped ids in the logged batch) at every log
    # boundary in this regime; watch it when tuning the factor.
    a2a_capacity_factor: float = 0.0
    # attention core for sequence models: "full" (T x T), "ring"
    # (sequence-parallel over the seq mesh axis; XLA blockwise innards —
    # the fastest long-T path measured on v5e), "ring_flash" (ring with the
    # Pallas flash kernels inside each ring step; ~2.4x slower than "ring"
    # at dh=64 on v5e — builders' reading, round 4; no program in the
    # tree reproduces it), "flash"
    # (single-device Pallas O(T) kernel; compiled, its blocks are whole
    # 128-lane tiles, so T pads up to a multiple of 128 — max_len = 20
    # runs as one masked 128-block: it compiles and is exact, and wastes
    # ~6x the attention work that "full" does at that length)
    attn: str = "full"
    # ring attention only: chunk each ring step's local attention to
    # O(Tq x ring_block_k) logits with a rematerialised backward (0 = one
    # chunk per ring step).  Must divide the per-device sequence length.
    ring_block_k: int = 0
    # Megatron-style tensor parallelism over the model axis for the sequence
    # model's dense layers (feed-forward + vocab projection — the FLOPs peak
    # and biggest dense param).  A sharding-spec change only; GSPMD inserts
    # the collectives.  Beyond-reference capability (SURVEY.md §2.3: absent).
    tensor_parallel: bool = False
    # in-backward sparse optimizer for embedding tables in the DMP regime
    # (fbgemm EmbOptimType parity: the reference picks ADAM on GPU and SGD on
    # CPU, torchrec/train.py:187-195).  "rowwise_adagrad" stores ONE f32
    # accumulator per row (fbgemm EXACT_ROWWISE_ADAGRAD, the >=1e9-row
    # configuration).  Every kind composes with fat-line fused storage —
    # the packed-line geometry adapts to the kind's state width.
    sparse_optimizer: str = "adam"
    # TBE unique-then-expand lookup (gspmd mode only): ONE sort per table
    # array per step deduplicates the ids; the forward gathers only unique
    # rows (compact, cache-resident) and the update reuses the same mapping
    # — measured ~25% off the DLRM-Criteo step.  Identical numerics; ids
    # must be non-negative (every shipped ETL's contract).
    dedup_lookup: bool = False
    # stack PLAIN (non-fused) embedding tables sharing (dim, sharding) into
    # one array (the 2D analogue of the always-on fat-row stacking): a
    # many-table model (DLRM-Criteo, 26 tables) then pays ONE dedupe + ONE
    # gather/scatter per step instead of one per table.  Opt-in because it
    # changes checkpoint state keys.
    stack_tables: bool = False
    # vocab size above which DMP-regime tables use fused fat-line storage
    # (ops/pallas_kernels.line_layout + the in-place DMA update kernel,
    # available for EVERY sparse_optimizer kind); smaller tables take the
    # gather/scatter or one-hot MXU tiers.  0 fuses every table; -1 disables
    # fused storage entirely (every table stays plain 2D — the faster
    # choice at the DLRM-Criteo profile in the builders' round-4 readings,
    # docs/BUDGET.md "Fused fat-line findings").  The kernel
    # choice itself follows the platform of the devices the tables live on
    # (core/mesh.pallas_impl: the Mosaic kernel on TPU devices or an error,
    # the XLA formulation on CPU devices) — there is no "use pallas" switch
    # to misconfigure, and core/mesh.PALLAS_CHOICES records what ran.
    fused_table_threshold: int = 16384
    # [embeddings] table: frequency-partitioned hot/cold storage knobs
    embeddings: EmbeddingsSpec = field(default_factory=EmbeddingsSpec)
    # [train] table: train-loop pipelining knobs
    train: TrainSpec = field(default_factory=TrainSpec)
    # [lm] table: a causal decoder's architecture and share
    lm: LmSpec = field(default_factory=LmSpec)
    # [serving] table: online-inference knobs (launch serve / tdfo_tpu.serve)
    serving: ServingSpec = field(default_factory=ServingSpec)
    # [loadgen] table: load-generation harness knobs (launch loadgen)
    loadgen: LoadgenSpec = field(default_factory=LoadgenSpec)
    # [telemetry] table: flight-recorder knobs (tdfo_tpu/obs)
    telemetry: TelemetrySpec = field(default_factory=TelemetrySpec)
    # [online] table: serve -> retrain -> swap supervisor knobs
    online: OnlineSpec = field(default_factory=OnlineSpec)
    planner: PlannerSpec = field(default_factory=PlannerSpec)
    mesh: MeshSpec = field(default_factory=MeshSpec)

    # --- runtime knobs ---
    # compiled multi-step loop: each device dispatch runs this many train
    # steps (tensorflow2/utils.py steps_per_execution parity; a real TPU win
    # because per-step host round trips disappear)
    steps_per_execution: int = 1
    # jit_xla = false -> the whole fit runs under jax.disable_jit(): op-by-op
    # eager execution for debugging (tensorflow2/utils.py jit_compile=False
    # parity; None/true = compiled, the default and the only sane production
    # setting)
    jit_xla: bool | None = None
    # use_tpu = true -> fail fast at Trainer construction unless jax's
    # backend really is TPU (tensorflow2 TPUStrategy-resolution parity: the
    # reference connected to a TPU cluster or died; silently training a
    # "TPU" config on CPU is the failure mode this guards)
    use_tpu: bool = False
    # PS-strategy parity (tensorflow2/train_ps.py:55-58 MinSizePartitioner):
    # dense-regime variables whose per-shard size stays >= this many bytes
    # are sharded over the model axis; 0 disables.  "Parameter servers" are
    # just sharded arrays under GSPMD (SURVEY.md §2.3).
    ps_min_shard_bytes: int = 0
    checkpoint_dir: str | None = None
    checkpoint_every_n_epochs: int = 10
    # --- fault tolerance ---
    # step-granular checkpoints: every N train steps the full state PLUS the
    # data-stream cursor (epoch, batch offset) is saved, so a preempted run
    # resumes from the exact batch instead of replaying the epoch
    # (BackupAndRestore at step granularity, tensorflow2/train_ps.py:156).
    # 0 = epoch-granular only (checkpoint_every_n_epochs still applies).
    checkpoint_every_n_steps: int = 0
    # corrupted-shard quarantine: a shard that fails to open/decode is
    # skipped with a warning; the run fails only once MORE than this many
    # shards are bad.  0 = any bad shard is fatal (the pre-quarantine
    # behaviour).  Single-host semantics; on multi-host meshes a skipped
    # shard must be skipped identically by every host (shared storage).
    max_bad_shards: int = 0
    # non-finite guard: after K CONSECUTIVE non-finite train losses the
    # trainer restores the last good on-device state snapshot and skips the
    # offending batch window (a `rollback` record lands in metrics.jsonl)
    # instead of silently training on NaN optimizer state.  The guard
    # fetches losses in windows of K steps (one host sync per window).
    # 0 disables guard, snapshots, and syncs entirely.
    nonfinite_tolerance: int = 3
    # refresh the guard's on-device state snapshot every N steps (only at a
    # window boundary whose losses were all finite, so the snapshot is
    # known-good).  Copy cost is one HBM pass over the state — size this to
    # taste on multi-GB-table runs.  Ignored when nonfinite_tolerance = 0.
    snapshot_every_n_steps: int = 100
    # deterministic fault injection ([faults] config table): kill_at_step /
    # nan_at_step / fail_io_nth — see tdfo_tpu/utils/faults.py.  Test-only
    # by design, but honoured by every real run so crash/resume tests run
    # the exact production path.
    faults: FaultSpec = field(default_factory=FaultSpec)
    log_every_n_steps: int = 100
    profile: bool = False
    # mirror every logged scalar into a TensorBoard events file next to the
    # JSONL (tensorflow2/train_ps.py:154 TensorBoard-callback parity, made
    # framework-wide; TF-free writer, tdfo_tpu/utils/tensorboard.py):
    # `tensorboard --logdir <checkpoint_dir>` shows train/eval curves
    tensorboard: bool = False

    # --- preprocessing handshake ---
    size_map: Mapping[str, int] = field(default_factory=dict)

    @property
    def is_causal_lm(self) -> bool:
        """``model`` names a causal decoder family: the ``[lm]`` table, packed
        token sequences, a donated state, next-token loss."""
        return self.model in LM_FAMILIES

    def _check_lm(self) -> None:
        model = self.model
        for key in LM_FAMILIES[model]:
            if getattr(self.lm, key) <= 0:
                raise ValueError(f"model = \"{model}\" needs [lm] {key} > 0")
        if self.nonfinite_tolerance != 0:
            # the step donates its state (a dense state of many GiB cannot
            # live twice), so there is nothing for the guard to roll back to
            raise ValueError(
                f"model = \"{model}\" donates its train state: set "
                "nonfinite_tolerance = 0 (the non-finite guard keeps a second "
                "copy of the state, which this model's state has no room for)")
        if self.steps_per_execution != 1 or self.train.pipeline_overlap:
            raise ValueError(f"model = \"{model}\" runs single-step "
                             "dispatches: steps_per_execution = 1, no "
                             "train.pipeline_overlap")
        if self.write_format != "parquet":
            raise ValueError(f"model = \"{model}\" reads parquet only "
                             "(sequence columns are list-valued)")

    def __post_init__(self) -> None:
        if self.max_len < self.sliding_step:
            raise ValueError(
                f"max_len ({self.max_len}) must be >= sliding_step ({self.sliding_step})"
            )
        if self.write_format not in ("parquet", "tfrecord"):
            raise ValueError(f"unsupported write_format: {self.write_format!r}")
        if self.model not in ("twotower", "dlrm", "bert4rec", *LM_FAMILIES):
            raise ValueError(f"unknown model: {self.model!r}")
        if self.is_causal_lm:
            self._check_lm()
        elif self.lm != LmSpec():
            raise ValueError("the [lm] table configures a causal decoder "
                             f"(model = one of {sorted(LM_FAMILIES)}) only")
        if ((self.categorical_features or self.continuous_features)
                and self.model != "dlrm"):
            raise ValueError(
                "categorical_features/continuous_features define a custom CTR "
                "schema, which only the dlrm model consumes (twotower and "
                "bert4rec have fixed reference schemas)"
            )
        if self.model == "dlrm" and self.continuous_features and                 not self.categorical_features:
            raise ValueError(
                "continuous_features without categorical_features: a custom "
                "schema must name its embedding-table columns"
            )
        if self.embedding_sharding not in ("row", "column", "table", "replicated"):
            raise ValueError(f"unknown embedding_sharding: {self.embedding_sharding!r}")
        if self.lookup_mode not in ("gspmd", "psum", "alltoall"):
            raise ValueError(f"unknown lookup_mode: {self.lookup_mode!r}")
        if self.dedup_lookup and self.lookup_mode != "gspmd":
            raise ValueError("dedup_lookup composes with lookup_mode \"gspmd\" only")
        if self.a2a_capacity_factor < 0:
            raise ValueError("a2a_capacity_factor must be >= 0 (0 = exact)")
        if self.jagged and self.model != "bert4rec":
            raise ValueError("jagged=true is a sequence-model knob (bert4rec)")
        if self.model == "bert4rec" and self.write_format != "parquet":
            # the seq ETL writes list-valued columns, which the TFRecord
            # sidecar schema does not carry — rejected rather than silently
            # reading parquet anyway (every config key must DO something)
            raise ValueError(
                "model=\"bert4rec\" supports write_format=\"parquet\" only "
                "(sequence columns are list-valued)"
            )
        if self.attn not in ("full", "ring", "ring_flash", "flash"):
            raise ValueError(f"unknown attn: {self.attn!r}")
        if self.ring_block_k < 0:
            raise ValueError("ring_block_k must be >= 0 (0 = unchunked)")
        if self.ring_block_k and self.attn != "ring":
            raise ValueError("ring_block_k requires attn = \"ring\"")
        if self.sparse_optimizer not in ("adam", "sgd", "adagrad",
                                         "rowwise_adagrad"):
            raise ValueError(f"unknown sparse_optimizer: {self.sparse_optimizer!r}")
        _storage_dtypes = ("float32", "bfloat16", "int8")
        emb = self.embeddings
        for label, dt in (("table_dtype", emb.table_dtype),
                          *((f"table_dtype_overrides[{n!r}]", d)
                            for n, d in emb.table_dtype_overrides)):
            if dt not in _storage_dtypes:
                raise ValueError(
                    f"embeddings {label} must be one of {_storage_dtypes}, "
                    f"got {dt!r}")
        if emb.slot_dtype not in ("float32", "bfloat16"):
            # int8 slots would put second-moment state on a per-row grid the
            # optimizer math cannot survive (ops/quant.py module docstring)
            raise ValueError(
                "embeddings slot_dtype must be one of ('float32', "
                f"'bfloat16'), got {emb.slot_dtype!r}")
        _any_int8 = (emb.table_dtype == "int8"
                     or any(d == "int8" for _, d in emb.table_dtype_overrides))
        # int8 composes with the update cache (rows admitted dequantized,
        # requantized per row at write time, codes + sidecar bit-copied at
        # flush) and with hot/cold (the full-block one-hot update only ever
        # touches the f32 hot HEAD; the cold residual stays row-sparse int8)
        # — both former refusals lifted; the cache mirrors the sidecar in a
        # "qs" buffer and hot heads dequantize at init.
        if (_any_int8 and self.sparse_optimizer == "rowwise_adagrad"
                and self.fused_table_threshold != -1):
            raise ValueError(
                'table_dtype = "int8" with sparse_optimizer = '
                '"rowwise_adagrad" cannot use fused fat-line storage: the '
                "f32 per-row accumulator contract cannot ride a quantized "
                "line.  Set fused_table_threshold = -1 (disable fusing) or "
                "pick sparse_optimizer = adagrad/adam/sgd")
        if (emb.slot_dtype == "bfloat16"
                and self.sparse_optimizer == "rowwise_adagrad"):
            raise ValueError(
                'slot_dtype = "bfloat16" cannot combine with '
                'sparse_optimizer = "rowwise_adagrad": that kind stores ONE '
                "f32 accumulator per row (the fbgemm EXACT_ROWWISE_ADAGRAD "
                "parity contract), so quantizing the slot buffer is refused")
        if (emb.table_dtype != "float32" or emb.slot_dtype != "float32"
                or any(d != "float32"
                       for _, d in emb.table_dtype_overrides)):
            if not (self.model == "dlrm"
                    or (self.model == "twotower" and self.model_parallel)):
                raise ValueError(
                    "embeddings table_dtype/slot_dtype configure the DMP "
                    "sparse regime (dlrm, or twotower with model_parallel "
                    "= true); other regimes would silently ignore the knob")
        if self.steps_per_execution < 1:
            raise ValueError("steps_per_execution must be >= 1")
        if self.checkpoint_every_n_steps < 0:
            raise ValueError(
                "checkpoint_every_n_steps must be >= 0 (0 = epoch-granular)")
        if self.max_bad_shards < 0:
            raise ValueError("max_bad_shards must be >= 0 (0 = fail on any)")
        if self.nonfinite_tolerance < 0:
            raise ValueError(
                "nonfinite_tolerance must be >= 0 (0 = guard disabled)")
        if self.snapshot_every_n_steps < 1:
            raise ValueError("snapshot_every_n_steps must be >= 1")
        if not self.streaming and self.write_format != "parquet":
            raise ValueError("streaming=false (map-style) requires parquet data")
        if self.fused_table_threshold < -1:
            raise ValueError(
                "fused_table_threshold must be >= 0 (0 = fuse every table) "
                "or exactly -1 (disable fused storage)")
        if self.embeddings.hot_vocab < 0:
            raise ValueError("hot_vocab must be >= 0 (0 = hot/cold disabled)")
        if not (0.0 < self.embeddings.hot_fraction <= 1.0):
            raise ValueError("hot_fraction must be in (0, 1]")
        if self.embeddings.cache_rows < 0:
            raise ValueError("cache_rows must be >= 0 (0 = update cache off)")
        if self.embeddings.flush_every < 1:
            raise ValueError("flush_every must be >= 1 (steps between cache "
                             "write-backs)")
        if self.embeddings.cache_rows > 0:
            if not (self.model == "dlrm"
                    or (self.model == "twotower" and self.model_parallel)):
                raise ValueError(
                    "cache_rows > 0 configures the DMP sparse regime (dlrm, "
                    "or twotower with model_parallel = true); other regimes "
                    "would silently ignore the knob")
            if self.lookup_mode != "gspmd":
                raise ValueError(
                    "the update cache (cache_rows > 0) composes with "
                    "lookup_mode \"gspmd\" only: cache directory routing and "
                    "the hit overlay run inside the jitted step, which the "
                    "explicit psum/alltoall shard_map programs (and the "
                    "grouped exchange) do not carry")
            if self.steps_per_execution != 1:
                raise ValueError(
                    "cache_rows > 0 requires steps_per_execution = 1: the "
                    "trainer schedules flushes between steps, which a "
                    "compiled multi-step loop would skip")
            if self.train.pipeline_overlap:
                raise ValueError(
                    "the update cache (cache_rows > 0) does not compose "
                    "with train.pipeline_overlap: the pipelined step runs "
                    "the grouped alltoall exchange, not lookup_mode "
                    "\"gspmd\"")
        if self.embeddings.hot_vocab > 0 and self.lookup_mode != "gspmd":
            raise ValueError(
                "hot/cold embedding storage (hot_vocab > 0) composes with "
                "lookup_mode \"gspmd\" only: hot tables are replicated and "
                "routed inside the jitted step, which the explicit psum/"
                "alltoall shard_map programs do not carry")
        if self.embeddings.grouped_a2a:
            if self.lookup_mode != "alltoall":
                raise ValueError(
                    "grouped_a2a groups the alltoall exchange and therefore "
                    "requires lookup_mode = \"alltoall\"")
            if not self.model_parallel:
                raise ValueError(
                    "grouped_a2a requires model_parallel = true: without "
                    "sharded tables there is no exchange to group")
        if self.serving.top_k < 1:
            raise ValueError("serving top_k must be >= 1")
        if self.serving.coarse_k < 0:
            raise ValueError(
                "serving coarse_k must be >= 0 (0 = exact single-stage "
                "retrieval)")
        if self.serving.coarse_k and self.serving.coarse_k < self.serving.top_k:
            raise ValueError(
                "serving coarse_k must be >= top_k: the coarse stage must "
                "hand the re-rank at least top_k candidates "
                f"(coarse_k={self.serving.coarse_k}, "
                f"top_k={self.serving.top_k})")
        if self.serving.coarse_dtype not in _storage_dtypes:
            raise ValueError(
                f"serving coarse_dtype must be one of {_storage_dtypes}, "
                f"got {self.serving.coarse_dtype!r}")
        if self.serving.corpus_batch < 1:
            raise ValueError("serving corpus_batch must be >= 1")
        if self.serving.max_batch < 1:
            raise ValueError("serving max_batch must be >= 1")
        if self.serving.batch_deadline_ms < 0:
            raise ValueError(
                "serving batch_deadline_ms must be >= 0 (0 = ship every "
                "request immediately)")
        if not self.serving.buckets:
            raise ValueError("serving buckets must name at least one shape")
        if any(b < 1 for b in self.serving.buckets):
            raise ValueError("serving buckets must be positive batch shapes")
        if list(self.serving.buckets) != sorted(set(self.serving.buckets)):
            raise ValueError(
                "serving buckets must be strictly increasing (each padded "
                "shape compiles one program; duplicates/disorder hide that)")
        if self.serving.max_queue < 0:
            raise ValueError(
                "serving max_queue must be >= 0 (0 = unbounded admission)")
        if self.serving.shed_policy not in ("oldest", "reject"):
            raise ValueError(
                "serving shed_policy must be 'oldest' or 'reject', got "
                f"{self.serving.shed_policy!r}")
        if self.serving.swap_poll_s < 0:
            raise ValueError(
                "serving swap_poll_s must be >= 0 (0 = poll every tick)")
        if self.serving.max_bad_deltas < 1:
            raise ValueError(
                "serving max_bad_deltas must be >= 1 (how many consecutive "
                "corrupt deltas flip degraded mode)")
        if self.serving.max_batch > self.serving.buckets[-1]:
            raise ValueError(
                "serving max_batch must fit the largest bucket: a full batch "
                f"of {self.serving.max_batch} rows cannot pad into "
                f"buckets[-1] = {self.serving.buckets[-1]}")
        if self.serving.log_segment_bytes < 0:
            raise ValueError(
                "serving log_segment_bytes must be >= 0 (0 = one unbounded "
                "request-log segment)")
        if self.serving.log_segment_bytes and not self.serving.log_features:
            raise ValueError(
                "serving log_segment_bytes rotates the replayable request "
                "log, which only exists with log_features = true")
        if self.serving.replicas < 1:
            raise ValueError(
                "serving replicas must be >= 1 (1 = the single-frontend "
                "layout)")
        if self.serving.keep_versions < 0:
            raise ValueError(
                "serving keep_versions must be >= 0 (0 = keep every "
                "published version)")
        if self.serving.fleet_mode not in ("inproc", "process"):
            raise ValueError(
                "serving fleet_mode must be 'inproc' or 'process', got "
                f"{self.serving.fleet_mode!r}")
        if self.serving.fleet_mode == "process" and self.serving.replicas < 2:
            raise ValueError(
                "serving fleet_mode = 'process' requires replicas >= 2: a "
                "one-process fleet has no survivors to degrade to — use the "
                "single-frontend 'inproc' layout instead")
        if self.serving.heartbeat_stale_ms <= 0:
            raise ValueError(
                "serving heartbeat_stale_ms must be > 0: the balancer needs "
                "a finite staleness bound to evict silent replicas")
        if self.serving.max_frame_bytes < 1024:
            raise ValueError(
                "serving max_frame_bytes must be >= 1024 (the wire refuses "
                "frames beyond it; smaller caps cannot carry a sync message)")
        if self.serving.connect_retries < 1:
            raise ValueError("serving connect_retries must be >= 1")
        if self.serving.connect_base_ms <= 0:
            raise ValueError("serving connect_base_ms must be > 0")
        if self.serving.respawn_base_ms <= 0:
            raise ValueError("serving respawn_base_ms must be > 0")
        if self.serving.respawn_max_ms < self.serving.respawn_base_ms:
            raise ValueError(
                "serving respawn_max_ms must be >= respawn_base_ms (it caps "
                "the respawn backoff schedule)")
        if self.serving.flap_window_s <= 0:
            raise ValueError("serving flap_window_s must be > 0")
        if self.serving.flap_max_deaths < 2:
            raise ValueError(
                "serving flap_max_deaths must be >= 2: one death must never "
                "quarantine a replica (every kill drill dies exactly once)")
        if self.serving.model_kind not in ("auto", "ctr", "seq"):
            raise ValueError(
                "serving model_kind must be 'auto', 'ctr' or 'seq', got "
                f"{self.serving.model_kind!r}")
        if self.serving.model_kind == "ctr" and self.model == "bert4rec":
            raise ValueError(
                "serving model_kind = 'ctr' does not match model = "
                "'bert4rec': the seq family exports a bert4rec bundle — set "
                "model_kind to 'seq' (or 'auto')")
        if (self.serving.model_kind == "seq"
                and self.model not in ("bert4rec",)):
            raise ValueError(
                f"serving model_kind = 'seq' does not match model = "
                f"{self.model!r}: only bert4rec exports a sequence bundle — "
                "set model_kind to 'ctr' (or 'auto')")
        if self.serving.max_history < 0:
            raise ValueError(
                "serving max_history must be >= 0 (0 = the full max_len - 1 "
                "eval window)")
        if self.serving.max_history > self.max_len - 1:
            raise ValueError(
                "serving max_history must leave room for the appended MASK "
                f"position: <= max_len - 1 = {self.max_len - 1}, got "
                f"{self.serving.max_history}")
        if self.serving.history_buckets:
            if any(b < 1 for b in self.serving.history_buckets):
                raise ValueError(
                    "serving history_buckets must be positive batch shapes")
            if (list(self.serving.history_buckets)
                    != sorted(set(self.serving.history_buckets))):
                raise ValueError(
                    "serving history_buckets must be strictly increasing "
                    "(each padded shape compiles one program; duplicates/"
                    "disorder hide that)")
        if self.loadgen.mode not in ("closed", "open"):
            raise ValueError(
                "loadgen mode must be 'closed' or 'open', got "
                f"{self.loadgen.mode!r}")
        if self.loadgen.requests < 1:
            raise ValueError("loadgen requests must be >= 1")
        if self.loadgen.concurrency < 1:
            raise ValueError("loadgen concurrency must be >= 1")
        if self.loadgen.rate_qps <= 0:
            raise ValueError("loadgen rate_qps must be > 0")
        if self.loadgen.zipf_a <= 1.0:
            raise ValueError(
                "loadgen zipf_a must be > 1 (the zipf popularity exponent; "
                "<= 1 has no normalizable tail)")
        if self.loadgen.rows_per_request < 1:
            raise ValueError("loadgen rows_per_request must be >= 1")
        if self.loadgen.p99_slo_ms <= 0:
            raise ValueError(
                "loadgen p99_slo_ms must be > 0 (the SLO the knee is "
                "measured against)")
        if self.telemetry.stall_timeout_s < 0:
            raise ValueError(
                "telemetry stall_timeout_s must be >= 0 (0 = watchdog off)")
        if self.telemetry.log_rotate_bytes < 0:
            raise ValueError(
                "telemetry log_rotate_bytes must be >= 0 (0 = unbounded "
                "metrics/retries JSONL)")
        if self.online.steps_per_cycle < 1:
            raise ValueError("online steps_per_cycle must be >= 1")
        if self.online.max_cycles < 0:
            raise ValueError(
                "online max_cycles must be >= 0 (0 = drain the log)")
        if self.online.max_bad_records < 0:
            raise ValueError(
                "online max_bad_records must be >= 0 (0 = fail on any)")
        if self.online.max_lag_records < 0:
            raise ValueError(
                "online max_lag_records must be >= 0 (0 = unbounded lag)")
        if self.online.lag_policy not in ("fail", "skip"):
            raise ValueError(
                "online lag_policy must be 'fail' or 'skip', got "
                f"{self.online.lag_policy!r}")
        if self.online.request_log and not self.checkpoint_dir:
            raise ValueError(
                "online.request_log requires checkpoint_dir: the replay "
                "cursor persists as a checkpoint sidecar — without it the "
                "loop cannot be crash-safe")
        if self.online.canary_cycles < 0:
            raise ValueError(
                "online canary_cycles must be >= 0 (0 = ungated publish)")
        if self.online.canary_cycles:
            if self.serving.replicas < 2:
                raise ValueError(
                    "online canary_cycles requires serving replicas >= 2: "
                    "the canary verdict compares canary replicas against "
                    "stable ones, which a single frontend cannot stage")
            if self.serving.keep_versions == 1:
                raise ValueError(
                    "online canary_cycles requires serving keep_versions "
                    "of 0 (unbounded) or >= 2: the watch window needs the "
                    "last good version AND the canary candidate on disk")
        if not (0.0 < self.online.canary_fraction < 1.0):
            raise ValueError(
                "online canary_fraction must be in (0, 1): at least one "
                "canary replica, never the whole fleet "
                f"(got {self.online.canary_fraction})")
        if self.online.max_auc_regression < 0:
            raise ValueError(
                "online max_auc_regression must be >= 0 (the tolerated "
                "held-out/canary AUC drop)")
        if self.online.max_p99_regression_ms < 0:
            raise ValueError(
                "online max_p99_regression_ms must be >= 0 (0 disables the "
                "latency verdict term; positive = the tolerated canary-over-"
                "stable heartbeat p99 excess in milliseconds)")
        if self.online.shadow_eval_batches < 1:
            raise ValueError(
                "online shadow_eval_batches must be >= 1: the gate needs "
                "at least one held-out batch to score")
        if self.online.keep_consumed_segments < 0:
            raise ValueError(
                "online keep_consumed_segments must be >= 0 (0 = keep "
                "every sealed segment)")
        if self.planner.hbm_gb < 0:
            raise ValueError(
                "planner hbm_gb must be >= 0 (0 = unlimited device memory)")
        if self.planner.n_devices < 1:
            raise ValueError("planner n_devices must be >= 1")
        if self.planner.plan:
            if not (self.model == "dlrm"
                    or (self.model == "twotower" and self.model_parallel)):
                raise ValueError(
                    "planner.plan configures the DMP sparse regime (dlrm, "
                    "or twotower with model_parallel = true); other regimes "
                    "would silently ignore the plan")
            if self.lookup_mode != "gspmd":
                raise ValueError(
                    "planner.plan composes with lookup_mode \"gspmd\" only: "
                    "planned placements (replicated tables, hot heads, "
                    "table-wise assignment) route inside the jitted step")
            # the plan OWNS the per-table levers; a config that also sets
            # them by hand would be silently overridden — refuse instead
            if self.embeddings.hot_vocab > 0:
                raise ValueError(
                    "planner.plan conflicts with embeddings.hot_vocab > 0: "
                    "the plan embeds its own per-table hot splits")
            if self.embeddings.cache_rows > 0:
                raise ValueError(
                    "planner.plan conflicts with embeddings.cache_rows > 0: "
                    "the plan prices the update cache itself and carries "
                    "its own cache_rows/cache_flush_every decision (> 0 "
                    "only for plain-int8 plans where the model predicts a "
                    "win)")
            if (self.embeddings.table_dtype != "float32"
                    or self.embeddings.slot_dtype != "float32"
                    or self.embeddings.table_dtype_overrides):
                raise ValueError(
                    "planner.plan conflicts with hand-set embeddings "
                    "table_dtype/slot_dtype/table_dtype_overrides: storage "
                    "dtypes are per-table plan decisions")
        if self.train.pipeline_overlap:
            if not self.embeddings.grouped_a2a:
                raise ValueError(
                    "pipeline_overlap pipelines the grouped input-dist and "
                    "therefore requires [embeddings] grouped_a2a = true "
                    "(and lookup_mode = \"alltoall\")")
            if self.steps_per_execution != 1:
                raise ValueError(
                    "pipeline_overlap carries the next batch's input-dist "
                    "across step boundaries and composes with "
                    "steps_per_execution = 1 only")

    @property
    def effective_fused_threshold(self) -> int | None:
        """Vocab threshold for fused fat-line storage, or ``None`` when
        ``fused_table_threshold = -1`` disables fusion outright.  The packed
        line geometry adapts to the optimizer kind
        (``ops/pallas_kernels.line_layout``), so every sparse-optimizer
        kind gets the fused in-place DMA update path."""
        if self.fused_table_threshold == -1:
            return None
        return self.fused_table_threshold

    @property
    def global_train_batch_size(self) -> int:
        import jax

        return self.per_device_train_batch_size * jax.device_count()

    def replace(self, **kwargs: Any) -> "Config":
        return dataclasses.replace(self, **kwargs)


def load_size_map(data_dir: Path) -> dict[str, int]:
    """Load the preprocessing -> training vocab-size contract if present."""
    path = Path(data_dir) / "size_map.json"
    if path.exists():
        with open(path) as f:
            return {k: int(v) for k, v in json.load(f).items()}
    return {}


_CONFIG_FIELDS = {f.name for f in dataclasses.fields(Config)}
_MESH_FIELDS = {f.name for f in dataclasses.fields(MeshSpec)} - {"axis_names"}
_FAULT_FIELDS = {f.name for f in dataclasses.fields(FaultSpec)}
_EMBEDDINGS_FIELDS = {f.name for f in dataclasses.fields(EmbeddingsSpec)}
_TRAIN_FIELDS = {f.name for f in dataclasses.fields(TrainSpec)}
_LM_FIELDS = {f.name for f in dataclasses.fields(LmSpec)}
_SERVING_FIELDS = {f.name for f in dataclasses.fields(ServingSpec)}
_LOADGEN_FIELDS = {f.name for f in dataclasses.fields(LoadgenSpec)}
_TELEMETRY_FIELDS = {f.name for f in dataclasses.fields(TelemetrySpec)}
_ONLINE_FIELDS = {f.name for f in dataclasses.fields(OnlineSpec)}
_PLANNER_FIELDS = {f.name for f in dataclasses.fields(PlannerSpec)}


def read_configs(config_path: str | os.PathLike | None = None, **overrides: Any) -> Config:
    """Read ``config.toml`` (flat keys, reference-compatible) into a Config.

    Reference-compatible behaviours preserved:
      * flat toml keys (no sections required); unknown keys are rejected so
        typos fail loudly (the reference dataclasses did this implicitly).
      * ``size_map.json`` next to the data dir merged in when it exists.
      * a ``[mesh]`` table maps onto :class:`MeshSpec` (new capability).
    """
    raw: dict[str, Any] = {}
    if config_path is not None:
        with open(config_path, "rb") as f:
            raw = tomllib.load(f)
    raw.update(overrides)

    mesh_raw = raw.pop("mesh", {})
    if isinstance(mesh_raw, MeshSpec):
        mesh = mesh_raw
    else:
        unknown_mesh = set(mesh_raw) - _MESH_FIELDS
        if unknown_mesh:
            raise ValueError(f"unknown mesh config keys: {sorted(unknown_mesh)}")
        mesh = MeshSpec(**mesh_raw)

    faults_raw = raw.pop("faults", {})
    if isinstance(faults_raw, FaultSpec):
        faults = faults_raw
    else:
        unknown_faults = set(faults_raw) - _FAULT_FIELDS
        if unknown_faults:
            raise ValueError(
                f"unknown faults config keys: {sorted(unknown_faults)}")
        faults = FaultSpec(**faults_raw)

    emb_raw = raw.pop("embeddings", {})
    if isinstance(emb_raw, EmbeddingsSpec):
        embeddings = emb_raw
    else:
        unknown_emb = set(emb_raw) - _EMBEDDINGS_FIELDS
        if unknown_emb:
            raise ValueError(
                f"unknown embeddings config keys: {sorted(unknown_emb)}")
        embeddings = EmbeddingsSpec(**emb_raw)

    train_raw = raw.pop("train", {})
    if isinstance(train_raw, TrainSpec):
        train = train_raw
    else:
        unknown_train = set(train_raw) - _TRAIN_FIELDS
        if unknown_train:
            raise ValueError(
                f"unknown train config keys: {sorted(unknown_train)}")
        train = TrainSpec(**train_raw)

    lm_raw = raw.pop("lm", {})
    if isinstance(lm_raw, LmSpec):
        lm = lm_raw
    else:
        unknown_lm = set(lm_raw) - _LM_FIELDS
        if unknown_lm:
            raise ValueError(f"unknown lm config keys: {sorted(unknown_lm)}")
        if "layer_types" in lm_raw:
            lm_raw = dict(lm_raw, layer_types=tuple(lm_raw["layer_types"]))
        lm = LmSpec(**lm_raw)

    serving_raw = raw.pop("serving", {})
    if isinstance(serving_raw, ServingSpec):
        serving = serving_raw
    else:
        unknown_serving = set(serving_raw) - _SERVING_FIELDS
        if unknown_serving:
            raise ValueError(
                f"unknown serving config keys: {sorted(unknown_serving)}")
        for tup_key in ("buckets", "history_buckets"):
            if tup_key in serving_raw:
                serving_raw = dict(
                    serving_raw, **{tup_key: tuple(serving_raw[tup_key])})
        serving = ServingSpec(**serving_raw)

    loadgen_raw = raw.pop("loadgen", {})
    if isinstance(loadgen_raw, LoadgenSpec):
        loadgen = loadgen_raw
    else:
        unknown_loadgen = set(loadgen_raw) - _LOADGEN_FIELDS
        if unknown_loadgen:
            raise ValueError(
                f"unknown loadgen config keys: {sorted(unknown_loadgen)}")
        loadgen = LoadgenSpec(**loadgen_raw)

    telemetry_raw = raw.pop("telemetry", {})
    if isinstance(telemetry_raw, TelemetrySpec):
        telemetry = telemetry_raw
    else:
        unknown_telemetry = set(telemetry_raw) - _TELEMETRY_FIELDS
        if unknown_telemetry:
            raise ValueError(
                f"unknown telemetry config keys: {sorted(unknown_telemetry)}")
        telemetry = TelemetrySpec(**telemetry_raw)

    online_raw = raw.pop("online", {})
    if isinstance(online_raw, OnlineSpec):
        online = online_raw
    else:
        unknown_online = set(online_raw) - _ONLINE_FIELDS
        if unknown_online:
            raise ValueError(
                f"unknown online config keys: {sorted(unknown_online)}")
        online = OnlineSpec(**online_raw)

    planner_raw = raw.pop("planner", {})
    if isinstance(planner_raw, PlannerSpec):
        planner = planner_raw
    else:
        unknown_planner = set(planner_raw) - _PLANNER_FIELDS
        if unknown_planner:
            raise ValueError(
                f"unknown planner config keys: {sorted(unknown_planner)}")
        planner = PlannerSpec(**planner_raw)

    unknown = set(raw) - _CONFIG_FIELDS
    if unknown:
        raise ValueError(f"unknown config keys: {sorted(unknown)}")

    if "data_dir" in raw:
        raw["data_dir"] = Path(raw["data_dir"]).expanduser()
    for key in ("categorical_features", "continuous_features"):
        if key in raw:
            raw[key] = tuple(raw[key])  # toml arrays / lists -> tuples

    cfg = Config(mesh=mesh, faults=faults, embeddings=embeddings, train=train,
                 lm=lm, serving=serving, loadgen=loadgen, telemetry=telemetry,
                 online=online, planner=planner, **raw)
    if not cfg.size_map:
        size_map = load_size_map(cfg.data_dir)
        if size_map:
            cfg = cfg.replace(size_map=size_map)
    return cfg
