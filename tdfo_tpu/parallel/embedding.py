"""ShardedEmbeddingCollection — the TPU-native DistributedModelParallel core.

Re-designs torchrec's embedding stack (``EmbeddingConfig`` ->
``EmbeddingCollection`` -> ``EmbeddingCollectionSharder`` -> ``DMP``,
``torchrec/models.py:150-161`` + ``torchrec/train.py:235-254``) for GSPMD:
tables are plain arrays with sharding specs on a named mesh, and the lookup is
either compiler-scheduled (GSPMD inserts the collectives) or an explicit
``shard_map`` program using XLA collectives over ICI — replacing NCCL
all-to-all (SURVEY.md §2.2, §2.3).

Sharding strategies (torchrec parity):
  * ``row``        - vocab dim split over the ``model`` axis (ROW_WISE).
  * ``column``     - embedding dim split over the ``model`` axis (COLUMN_WISE).
  * ``table``      - whole tables placed on single model-axis slots
                     (TABLE_WISE), expressed TPU-natively by stacking the
                     group's tables into one row-sharded super-array whose
                     shard boundaries coincide with table boundaries.
  * ``replicated`` - every device holds the full table (DATA_PARALLEL).

Fused fat-row tables sharing (embedding_dim, sharding) are STACKED into one
``__fatstack_{d}_{sharding}`` array — fbgemm's table-BATCHED embedding
(TBE) design: the train step's per-array grouping then pays ONE dedupe and
ONE in-place DMA kernel launch per step for the whole group (measured ~0.3
ms off the v5e headline step vs per-table updates).

Lookup modes:
  * ``gspmd``    - ``jnp.take`` under jit; XLA partitions the gather and
                   inserts all-gather/all-to-all as needed.  Default; fuses
                   with downstream compute.
  * ``psum``     - explicit shard_map: ids replicated over ``model`` (batch
                   sharded over ``data``), each device gathers the rows it
                   owns, zeros elsewhere, then ``psum`` over ``model``.  One
                   collective; the idiomatic choice when batch x model are
                   different mesh axes.
  * ``alltoall`` - explicit shard_map for the torchrec regime where the batch
                   is sharded over the SAME axis as the tables: bucket ids by
                   owner shard, ``all_to_all`` the ids, gather locally,
                   ``all_to_all`` the vectors back (input-dist / output-dist
                   parity with DMP's NCCL plan, ``torchrec/train.py:241-247``).

``grouped_a2a=True`` upgrades the alltoall mode to torchrec's GROUPED
KJTAllToAll input-dist: every row/table-sharded table's ids ride one
offset-shifted virtual id stream through ONE owner sort and ONE id
``all_to_all`` (+ one for the returned vectors) per step — O(1) collectives
per direction instead of O(tables) — and :meth:`grouped_update` gives the
backward the same single grouped id+grad exchange.  The id half
(:meth:`grouped_input_dist`) never reads the tables, which is what makes
cross-batch input-dist pipelining legal (``train/sparse_step.py``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tdfo_tpu.core.mesh import MODEL_AXIS, mesh_platform, shard_map
from tdfo_tpu.ops.quant import dequantize_rows, quantize_rows

__all__ = ["EmbeddingSpec", "ShardedEmbeddingCollection", "make_embedding_specs"]


def make_embedding_specs(
    size_map,
    entries,
    embed_dim: int,
    sharding: str = "row",
    fused_threshold: int | None = 16384,
) -> "list[EmbeddingSpec]":
    """One table per ``(size_map key, table name, input column)`` entry —
    the single source of truth for the CTR families' init and fusion policy:
    glorot-bound uniform init ``sqrt(6 / (V + D))`` (init-equivalent to the
    dense regime's ``nn.Embed``), fat-row fused storage above
    ``fused_threshold`` rows (``None`` disables)."""
    import math

    specs = []
    for key, name, column in entries:
        vocab = int(size_map[key])
        specs.append(EmbeddingSpec(
            name=name,
            num_embeddings=vocab,
            embedding_dim=embed_dim,
            features=(column,),
            sharding=sharding,
            init_scale=math.sqrt(6.0 / (vocab + embed_dim)),
            fused=(fused_threshold is not None
                   and sharding in ("row", "replicated")
                   and vocab > fused_threshold),
        ))
    return specs


@dataclass(frozen=True)
class EmbeddingSpec:
    """torchrec ``EmbeddingConfig`` parity (torchrec/models.py:150-157)."""

    name: str
    num_embeddings: int
    embedding_dim: int
    features: tuple[str, ...] = ()
    sharding: str = "row"
    # uniform(-init_scale, init_scale); torchrec weight_init_min/max = -1/1
    init_scale: float = 1.0
    dtype: jnp.dtype = jnp.float32
    # fused in-backward optimizer storage: the table lives as packed fat
    # lines [L, T, 128] carrying [table | optimizer state] per vocab row
    # (ops/pallas_kernels.line_layout, geometry set by the collection's
    # fused_kind) so the optimizer read-modify-writes one aligned DMA
    # descriptor per touched line — the fbgemm-TBE-equivalent layout that
    # makes O(batch) updates fast on TPU for every EmbOptimType kind
    # (adam / sgd / adagrad / rowwise_adagrad).  Storage dtype follows
    # ``dtype`` (f32 or bf16; bf16 lines pack the optimizer state narrow
    # too, so fused rowwise_adagrad — whose accumulator is contractually
    # f32 — rejects bf16 at collection construction).
    fused: bool = False

    def feature_names(self) -> tuple[str, ...]:
        return self.features or (self.name,)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@dataclass(frozen=True)
class _A2AGroup:
    """Static plan of one grouped-alltoall exchange: all features whose
    tables share ``(embedding_dim, dtype)`` ride one virtual id stream.

    Per-array ``rows_per_shard`` (vocab rows each model shard owns, derived
    statically — never from live table values, so the id exchange carries no
    data dependency on the tables) and cumulative ``bases`` define disjoint
    per-shard virtual address ranges: feature id ``i`` of array ``a`` maps to
    ``owner = i // rps_a`` and virtual id ``i - owner * rps_a + base_a``; the
    owner decodes it back by base range."""

    key: str                               # ctx dict key, "{dim}_{dtype}"
    dim: int
    feats: tuple[str, ...]                 # input order (= update stream order)
    feat_meta: tuple[tuple[int, int], ...]  # per feat: (array idx, row offset)
    arrays: tuple[str, ...]                # init() pytree keys
    specs: tuple[EmbeddingSpec, ...]       # representative spec per array
    rows_per_shard: tuple[int, ...]        # per array
    bases: tuple[int, ...]                 # per array virtual base


def _a2a_bucket_cap(n: int, m: int, cf: float | None) -> int:
    """Per-owner send-bucket capacity of the alltoall lookup program for a
    local batch of ``n`` ids over ``m`` shards under capacity factor ``cf``
    (``None`` = exact worst case ``n``).  Bounded buckets round up to a
    sublane-friendly multiple of 8, never past ``n``.  The ONE definition
    shared by ``_lookup_alltoall`` (which sizes the real send buffers) and
    ``a2a_overflow`` (which counts dropped ids) — any drift between the two
    would silently mis-report the knob's failure mode."""
    cap = n if cf is None else min(n, max(1, int(cf * n / m)))
    if cap < n:
        cap = min(n, -(-cap // 8) * 8)
    return cap


# state.slots key prefix of a cached array's update-cache pytree
# (``SparseOptimizer.cache_init``).  Riding inside the existing slots dict
# keeps the train-state STRUCTURE unchanged when the cache is off, so
# legacy checkpoints restore and the default graphs stay byte-identical.
CACHE_PREFIX = "__cache__/"

# ``init()`` pytree key prefix of an int8 array's per-row (scale, offset)
# sidecar (f32 [V, 2]; column 0 = scale, column 1 = offset — the fbgemm
# rowwise-int8 TBE layout, see ``ops/quant.quantize_rows``).  The sidecar
# rides the TABLES dict — not slots — because ``init()`` computes it from
# the freshly drawn f32 rows, while slots are created later from the int8
# data alone; it shards with its parent array's vocab axis.
QSCALE_PREFIX = "__qscale__/"


def qscale_name(array_name: str) -> str:
    """Tables-dict key of ``array_name``'s int8 (scale, offset) sidecar."""
    return QSCALE_PREFIX + array_name


def _spec_is_int8(spec: "EmbeddingSpec") -> bool:
    return jnp.dtype(spec.dtype) == jnp.int8


class ShardedEmbeddingCollection:
    """A set of embedding tables with mesh shardings + lookup programs.

    Functional: ``init`` returns the table pytree (dict name -> array, plus
    stacked groups), ``lookup`` maps feature ids -> vectors.  Gradients flow
    through ``lookup`` like any jnp op; the row-sparse in-backward update path
    lives in ``tdfo_tpu/train/sparse_step.py``.
    """

    def __init__(
        self,
        specs: list[EmbeddingSpec],
        mesh: Mesh | None = None,
        axis: str = MODEL_AXIS,
        a2a_capacity_factor: float | None = None,
        stack_tables: bool = False,
        fused_kind: str = "adam",
        hot_ids: Mapping[str, np.ndarray] | None = None,
        grouped_a2a: bool = False,
        cache_rows: int = 0,
    ):
        """``a2a_capacity_factor``: per-shard send-bucket capacity for the
        alltoall lookup program, as a multiple of the balanced share
        ``local_batch / n_shards``.  ``None`` keeps the exact worst case
        (capacity = local batch, correct for ANY skew); a finite factor
        (e.g. 2.0) shrinks the a2a payload by ~n_shards/factor at the cost
        that ids beyond a bucket's capacity resolve to ZERO vectors under
        extreme skew (torchrec-planner-style capacity semantics).

        ``stack_tables``: also stack PLAIN (non-fused) tables sharing
        (dim, sharding, dtype) into one ``__tablestack_`` array — the 2D
        analogue of the always-on fat stacking, so a many-table model
        (DLRM-Criteo: 26 tables) pays ONE dedupe + ONE gather/scatter per
        step instead of one per table.  Opt-in because it changes the state
        pytree layout (checkpoint keys).

        ``fused_kind``: the sparse-optimizer kind whose state the fused
        fat-line storage packs per row (``pallas_kernels.line_layout``) —
        it determines the line geometry, so it must match the
        ``SparseOptimizer`` used by the train step (fbgemm's TBE likewise
        bakes the optimizer into the table storage,
        ``torchrec/train.py:241-247``).

        Fat-table STACKING (unlike ``stack_tables``) is not a knob: fused
        storage is itself the opt-in (``fused_table_threshold``), and the
        checkpoint layout stamp (``train/checkpoint.py LAYOUT_VERSION``)
        refuses cross-layout resumes, so the stacking's state-key change
        cannot corrupt an old run silently.

        ``hot_ids``: frequency-partitioned hot/cold mode (fbgemm
        MANAGED_CACHING / FAE analogue, ``tdfo_tpu/data/hot_ids.py``) —
        a mapping of table OR feature name to the table's sorted hot-id
        array (the power-law head, K <= ~16k ids covering most lookup
        mass).  Each listed table splits into a small REPLICATED hot head
        ``{name}__hot`` ([K, D], its own ``init()`` entry, updated
        scatter-free via one-hot MXU contractions in the train step) and
        the unchanged cold array (hot rows stay as never-touched storage,
        so sharding plans, stacking and checkpoint shapes are identical to
        a non-hot/cold run).  Lookups route branch-free: contiguous
        ``[0, K)`` hot prefixes (the Criteo ETL layout) remap with one
        compare, general sets with one ``searchsorted(method="sort")``.
        Hot/cold composes with lookup mode ``gspmd`` only, and only with
        plain (non-fused) row/replicated tables.

        ``grouped_a2a``: route ``alltoall``-mode lookups for every
        row/table-sharded table through ONE grouped exchange per
        (dim, dtype) group (torchrec KJTAllToAll input-dist parity) instead
        of a 2-collective program per table; the train step then routes
        those tables' updates through :meth:`grouped_update` (one id + one
        grad ``all_to_all``).  Lookup values are identical to the per-table
        program; update numerics are the same operations in the same
        order when each table serves a single feature (every shipped
        schema: a few f32 ULP between the two compiled programs, see
        :meth:`grouped_update`) — tables shared by several
        features receive the same per-row grad addends in a different
        (shard-major instead of feature-major) summation order.

        ``cache_rows``: device-resident update cache (software
        ``MANAGED_CACHING``, fbgemm lxu-cache analogue) — every plain 2D
        big-table array carries a ``cache_rows``-row cache in the train
        state (:meth:`init_caches`): touched rows are admitted on miss
        (gather-only), updated scatter-free IN the cache
        (``SparseOptimizer.cache_update``), and written back to the big
        table in one coalesced scatter per flush interval.  Training stays
        bit-identical to the eager path; 0 disables (and compiles the
        existing byte-identical graphs)."""
        from tdfo_tpu.ops.pallas_kernels import line_layout

        self.fused_kind = fused_kind
        line_layout(1, fused_kind)  # validates the kind eagerly
        self.specs = {s.name: s for s in specs}
        if len(self.specs) != len(specs):
            raise ValueError("duplicate table names")
        self.mesh = mesh
        self.axis = axis
        # platform of the devices the tables live on: what every fat-line
        # update dispatches kernel / interpret / xla from
        self.platform = mesh_platform(mesh)
        # <= 0 means "exact" everywhere (the config knob documents 0 that
        # way) — never let 0.0 slip through as a 1-element bucket capacity
        if a2a_capacity_factor is not None and a2a_capacity_factor <= 0:
            a2a_capacity_factor = None
        self.a2a_capacity_factor = a2a_capacity_factor
        self.grouped_a2a = grouped_a2a
        if cache_rows < 0:
            raise ValueError("cache_rows must be >= 0")
        self.cache_rows = cache_rows
        self._grouped_plans: dict[tuple[str, ...], tuple[_A2AGroup, ...]] = {}
        self.n_shards = mesh.shape[axis] if mesh is not None else 1
        self._feature_to_table: dict[str, str] = {}
        for s in specs:
            if s.fused and s.sharding not in ("row", "replicated"):
                raise ValueError(
                    f"table {s.name!r}: fused storage supports row/replicated "
                    f"sharding, not {s.sharding!r}"
                )
            if s.fused and jnp.dtype(s.dtype) not in (
                    jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16),
                    jnp.dtype(jnp.int8)):
                raise ValueError(
                    f"table {s.name!r}: fused storage supports float32/"
                    f"bfloat16/int8, not {jnp.dtype(s.dtype).name}")
            if (s.fused and jnp.dtype(s.dtype) == jnp.bfloat16
                    and fused_kind == "rowwise_adagrad"):
                # fat lines pack table AND state at one dtype; the rowwise
                # accumulator is contractually f32 per row (fbgemm
                # EXACT_ROWWISE_ADAGRAD), so it cannot ride a bf16 line
                raise ValueError(
                    f"table {s.name!r}: fused rowwise_adagrad storage "
                    "cannot be bfloat16 (the per-row accumulator is f32 by "
                    "the fbgemm parity contract)")
            if (s.fused and _spec_is_int8(s)
                    and fused_kind == "rowwise_adagrad"):
                # mirror line_layout's refusal with the table name attached
                raise ValueError(
                    f"table {s.name!r}: fused int8 storage does not support "
                    "rowwise_adagrad (the f32 per-row accumulator contract "
                    "cannot ride a quantized line)")
            if _spec_is_int8(s) and s.sharding == "column":
                # the (scale, offset) pair is per FULL row; a column shard
                # would requantize partial rows against a whole-row grid
                raise ValueError(
                    f"table {s.name!r}: int8 storage supports row/"
                    "replicated/table sharding, not 'column'")
            for f in s.feature_names():
                if f in self._feature_to_table:
                    raise ValueError(f"feature {f!r} served by two tables")
                self._feature_to_table[f] = s.name

        # table-wise groups: stack same-dim tables into one row-sharded array
        # whose per-shard row count covers whole tables.
        self._table_wise = [s for s in specs if s.sharding == "table"]
        self._stack_rows: dict[str, tuple[int, int]] = {}  # name -> (group_offset, padded_rows)
        self._groups: dict[str, list[EmbeddingSpec]] = {}
        # fused-table stacks: fbgemm's table-BATCHED embedding design — all
        # fused fat-row tables sharing (dim, sharding) live in ONE [Vtot, T,
        # 128] array, so the whole group costs ONE dedupe and ONE in-place
        # DMA kernel launch per step instead of one per table (the train
        # step's per-array grouping makes that automatic).
        self._fat_groups: dict[str, tuple[str, int, list[EmbeddingSpec]]] = {}
        self._fat_member_to_stack: dict[str, str] = {}

        def build_stacks(members, fused: bool):
            by_key: dict[tuple, list[EmbeddingSpec]] = {}
            for s in members:
                # canonical dtype NAME ("float32"), never str(class): two
                # spellings of one dtype must land in one group — mixed
                # f32/bf16 tables must NOT concatenate into one stream —
                # and the name becomes a checkpoint key.  f32 fused groups
                # keep the historical un-suffixed name (byte-stable
                # checkpoints); bf16 fused stacks carry the dtype suffix.
                dt = ("" if fused and jnp.dtype(s.dtype) == jnp.float32
                      else jnp.dtype(s.dtype).name)
                by_key.setdefault(
                    (s.embedding_dim, s.sharding, dt), []).append(s)
            prefix = "__fatstack_" if fused else "__tablestack_"
            for (dim, shard_kind, dt), group in sorted(
                    by_key.items(), key=lambda kv: str(kv[0])):
                if len(group) < 2:
                    continue  # single tables keep their own array (and name)
                gname = (f"{prefix}{dim}_{shard_kind}" if fused and not dt
                         else f"{prefix}{dim}_{shard_kind}_{dt}")
                total = sum(s.num_embeddings for s in group)
                # fused stacks additionally round to whole LINES so shard
                # boundaries never split a packed line
                unit = self.fat_layout(dim, group[0].dtype).r if fused else 1
                if shard_kind == "row":
                    unit *= self.n_shards
                total = _round_up(total, unit)
                off = 0
                for s in group:
                    self._stack_rows[s.name] = (off, total)
                    self._fat_member_to_stack[s.name] = gname
                    off += s.num_embeddings
                self._fat_groups[gname] = (shard_kind, dim, group)

        build_stacks(
            [s for s in specs if s.fused and s.sharding in ("row", "replicated")],
            fused=True,
        )
        if stack_tables:
            build_stacks(
                [s for s in specs
                 if not s.fused and s.sharding in ("row", "replicated")],
                fused=False,
            )
        if self._table_wise:
            if mesh is None:
                raise ValueError("table-wise sharding requires a mesh")
            by_dim: dict[int, list[EmbeddingSpec]] = {}
            for s in self._table_wise:
                by_dim.setdefault(s.embedding_dim, []).append(s)
            for dim, group in by_dim.items():
                if len({s.dtype for s in group}) > 1:
                    raise ValueError(
                        "table-wise tables stacked into one array must share "
                        f"a dtype; got {[(s.name, s.dtype) for s in group]}"
                    )
                # shard slot i holds tables i, i+M, i+2M, ...; pad every slot
                # to the max slot height so boundaries align with shards.
                m = self.n_shards
                slots: list[list[EmbeddingSpec]] = [group[i::m] for i in range(m)]
                slot_rows = max(sum(s.num_embeddings for s in sl) for sl in slots) if group else 0
                slot_rows = max(slot_rows, 1)
                offsets = {}
                for i, sl in enumerate(slots):
                    off = i * slot_rows
                    for s in sl:
                        offsets[s.name] = off
                        off += s.num_embeddings
                for s in group:
                    self._stack_rows[s.name] = (offsets[s.name], slot_rows * m)
                self._groups[f"__stack_{dim}"] = group

        # hot/cold split state: table name -> sorted hot ids, plus the two
        # static remap classifications (exact [0, K) prefix -> one compare;
        # K == vocab -> no cold side at all)
        self.hot_ids: dict[str, np.ndarray] = {}
        self._hot_prefix: dict[str, bool] = {}
        self._hot_full: dict[str, bool] = {}
        for key, ids in (hot_ids or {}).items():
            tname = self._feature_to_table.get(key, key)
            spec = self.specs.get(tname)
            if spec is None:
                raise KeyError(
                    f"hot_ids key {key!r} names neither a table nor a feature")
            arr = np.asarray(ids, dtype=np.int32)
            if arr.ndim != 1 or arr.size == 0 or (
                    arr.size > 1 and np.any(np.diff(arr) <= 0)):
                raise ValueError(
                    f"table {tname!r}: hot ids must be a non-empty sorted "
                    "unique 1D array")
            if arr[0] < 0 or arr[-1] >= spec.num_embeddings:
                raise ValueError(
                    f"table {tname!r}: hot ids outside [0, "
                    f"{spec.num_embeddings})")
            if spec.fused or spec.sharding not in ("row", "replicated"):
                raise ValueError(
                    f"table {tname!r}: hot/cold supports plain (non-fused) "
                    f"row/replicated tables; got fused={spec.fused}, "
                    f"sharding={spec.sharding!r}")
            # int8 composes: only the COLD residual stores int8 (row-sparse
            # scatter updates); the hot head is always a small f32 array, so
            # the scatter-free one-hot full-block requantize never touches
            # an int8 grid
            if tname in self.hot_ids:
                raise ValueError(f"table {tname!r} given two hot-id sets")
            if self.hot_array_name(tname) in self.specs:
                raise ValueError(
                    f"table name {self.hot_array_name(tname)!r} collides "
                    f"with the hot head array of {tname!r}")
            self.hot_ids[tname] = arr
            k = int(arr.shape[0])
            self._hot_prefix[tname] = bool(arr[-1] == k - 1)  # == arange(k)
            self._hot_full[tname] = k == spec.num_embeddings

    # ----------------------------------------------------------- hot/cold

    @staticmethod
    def hot_array_name(tname: str) -> str:
        """``init()`` pytree key of a hot table's head array."""
        return f"{tname}__hot"

    def hot_tables(self) -> tuple[str, ...]:
        """Logical table names with a hot/cold split (sorted)."""
        return tuple(sorted(self.hot_ids))

    def hot_count(self, tname: str) -> int:
        """Hot-head rows of ``tname`` (0 when the table is not split)."""
        ids = self.hot_ids.get(tname)
        return 0 if ids is None else int(ids.shape[0])

    def hot_full(self, tname: str) -> bool:
        """True when EVERY id of ``tname`` is hot: the cold side is dead —
        the train step statically skips its gather, dedupe and update."""
        return self._hot_full.get(tname, False)

    def hot_digest(self) -> dict[str, str]:
        """Per-table hot-set fingerprints for the checkpoint ``stamps``
        sidecar (empty when no table is split)."""
        from tdfo_tpu.data.hot_ids import hot_ids_digest

        return hot_ids_digest(self.hot_ids) if self.hot_ids else {}

    def route_ids(self, feature: str, ids: jax.Array):
        """Split a feature's raw ids into ``(hot_pos, cold_ids)``.

        ``hot_pos[i]`` is the id's slot in the hot head, -1 for cold or
        padding ids; ``cold_ids[i]`` is the original id with hot hits
        replaced by -1 (the existing negative-id padding semantics: cold
        gathers clamp them, dedupe drops them, one-hot zeroes them — no
        new masking machinery anywhere downstream).  For an unsplit table
        returns ``(None, ids)``.  Remap is branch-free: exact ``[0, K)``
        prefixes pay one compare, general sets one
        ``searchsorted(method="sort")`` (0.14 vs 0.86 ms default at 8k on
        v5e) against the <= ~16k-entry sorted hot-id constant."""
        tname = self._feature_to_table.get(feature, feature)
        hids = self.hot_ids.get(tname)
        if hids is None:
            return None, ids
        k = hids.shape[0]
        neg = ids < 0
        if self._hot_full[tname]:
            return jnp.where(neg, -1, ids), jnp.full_like(ids, -1)
        if self._hot_prefix[tname]:
            hit = (~neg) & (ids < k)
            hot_pos = jnp.where(hit, ids, -1)
        else:
            sorted_hot = jnp.asarray(hids)  # [K] device constant
            pos = jnp.clip(
                jnp.searchsorted(sorted_hot, ids, method="sort"), 0, k - 1
            ).astype(jnp.int32)
            hit = (~neg) & (jnp.take(sorted_hot, pos) == ids)
            hot_pos = jnp.where(hit, pos, -1)
        return hot_pos, jnp.where(hit, -1, ids)

    # ---------------------------------------------------------------- init

    def fat_layout(self, d: int, dtype="float32"):
        """Packed-line geometry of fused storage at embedding dim ``d``
        under this collection's ``fused_kind``.  ``dtype`` selects the
        f32-lane layout (default) or the int8 byte-container layout (codes
        + in-line (scale, offset) sidecar + f32-byte optimizer state)."""
        from tdfo_tpu.ops.pallas_kernels import line_layout

        return line_layout(d, self.fused_kind, dtype)

    def fat_layout_for(self, array_name: str):
        return self.fat_layout(self.array_embedding_dim(array_name),
                               self._array_rep_spec(array_name).dtype)

    def table_sharding(self, spec: EmbeddingSpec) -> NamedSharding | None:
        if self.mesh is None:
            return None
        trailing = (None, None) if spec.fused else (None,)
        if spec.sharding == "row":
            return NamedSharding(self.mesh, P(self.axis, *trailing))
        if spec.sharding == "column":
            return NamedSharding(self.mesh, P(None, self.axis))
        if spec.sharding == "replicated":
            return NamedSharding(self.mesh, P())
        raise ValueError(spec.sharding)

    def init(self, rng: jax.Array) -> dict[str, jax.Array]:
        """Create all tables, placed with their shardings.

        Row-sharded vocab sizes are padded up to a multiple of the shard
        count (padding rows are valid storage, never referenced by real ids).
        """
        tables: dict[str, jax.Array] = {}
        fat_members = {
            s.name for _, _, group in self._fat_groups.values() for s in group
        }
        keys = jax.random.split(
            rng, len(self.specs) + len(self._groups) + len(self._fat_groups)
        )
        key_iter = iter(keys)
        for name, spec in self.specs.items():
            if spec.sharding == "table" or name in fat_members:
                continue
            rows = spec.num_embeddings
            unit = (self.fat_layout(spec.embedding_dim, spec.dtype).r
                    if spec.fused else 1)
            if spec.sharding == "row":
                unit *= self.n_shards
            rows = _round_up(rows, unit)
            dim = spec.embedding_dim
            if spec.sharding == "column" and dim % self.n_shards:
                raise ValueError(
                    f"table {name}: embedding_dim {dim} not divisible by "
                    f"{self.n_shards} column shards"
                )
            # int8 tables draw at f32 and round-to-nearest onto the rowwise
            # grid (deterministic, keyless — init has no step to fold), so a
            # same-seed int8 run starts from the quantization of the exact
            # f32 tables
            draw_dtype = jnp.float32 if _spec_is_int8(spec) else spec.dtype
            t = jax.random.uniform(
                next(key_iter), (rows, dim), draw_dtype,
                minval=-spec.init_scale, maxval=spec.init_scale,
            )
            if spec.fused:
                from tdfo_tpu.ops.pallas_kernels import fat_pack

                # [lines, T, 128]: optimizer state starts at zero.  int8
                # packs round-to-nearest onto the same rowwise grid as the
                # plain-int8 draw below, with the (scale, offset) sidecar
                # IN-LINE — no separate __qscale__/ array.
                t = fat_pack(t, kind=self.fused_kind, dtype=spec.dtype)
            sh = self.table_sharding(spec)
            if _spec_is_int8(spec) and not spec.fused:
                t, qs = quantize_rows(t)
                qsh = (None if self.mesh is None else NamedSharding(
                    self.mesh,
                    P(self.axis, None) if spec.sharding == "row" else P()))
                tables[qscale_name(name)] = (
                    jax.device_put(qs, qsh) if qsh is not None else qs)
            tables[name] = jax.device_put(t, sh) if sh is not None else t
        def assemble_stack(group, key, dtype):
            # each member table keeps its own init scale (slice-wise draws);
            # padding rows stay zero — valid storage, never referenced.
            # int8 stacks assemble at f32; the caller quantizes the whole
            # stack (padding rows are constant -> exact through the offset).
            draw = jnp.float32 if jnp.dtype(dtype) == jnp.int8 else dtype
            total = self._stack_rows[group[0].name][1]
            dim = group[0].embedding_dim
            t = jnp.zeros((total, dim), draw)
            for s, k in zip(group, jax.random.split(key, len(group))):
                off, _ = self._stack_rows[s.name]
                rows = jax.random.uniform(
                    k, (s.num_embeddings, dim), draw,
                    minval=-s.init_scale, maxval=s.init_scale,
                )
                t = jax.lax.dynamic_update_slice(t, rows, (off, 0))
            return t

        def place_stack(gname, arr, group, spec_p):
            # spec_p None => replicated; quantize int8 stacks AFTER assembly.
            # Only plain 2D stacks get the separate sidecar — a fused int8
            # stack arrives already byte-packed (sidecar in-line).
            if arr.ndim == 2 and jnp.dtype(arr.dtype) == jnp.float32 and any(
                    _spec_is_int8(s) for s in group):
                arr, qs = quantize_rows(arr)
                if self.mesh is not None:
                    qp = P(self.axis, None) if spec_p is not None else P()
                    qs = jax.device_put(qs, NamedSharding(self.mesh, qp))
                tables[qscale_name(gname)] = qs
            if self.mesh is not None:
                sh = NamedSharding(
                    self.mesh, spec_p if spec_p is not None else P())
                arr = jax.device_put(arr, sh)
            tables[gname] = arr

        for gname, group in self._groups.items():
            t = assemble_stack(group, next(key_iter), group[0].dtype)
            place_stack(gname, t, group, P(self.axis, None))
        for gname, (shard_kind, dim, group) in self._fat_groups.items():
            if gname.startswith("__fatstack_"):
                from tdfo_tpu.ops.pallas_kernels import fat_pack

                t = assemble_stack(group, next(key_iter), group[0].dtype)
                # [lines, T, 128]; int8 quantizes inside fat_pack (RTN, the
                # plain-int8 init grid) with the sidecar packed in-line
                arr = fat_pack(t, kind=self.fused_kind, dtype=group[0].dtype)
            else:  # plain 2D table stack (stack_tables=True)
                arr = assemble_stack(group, next(key_iter), group[0].dtype)
            trailing = (None,) * (arr.ndim - 1)
            spec_p = (P(self.axis, *trailing) if shard_kind == "row"
                      else None)
            place_stack(gname, arr, group, spec_p)
        # hot heads: a GATHER of the already-initialised cold rows (no extra
        # rng keys), so a hot/cold run's initial effective tables are
        # bit-identical to the same-seed non-hot/cold run — the property the
        # trajectory-equivalence tests assert.  The duplicated cold rows
        # become dead storage (never gathered, never updated).
        for tname in sorted(self.hot_ids):
            aname, spec, off = self.resolve_table(tname)
            idx = jnp.asarray(self.hot_ids[tname]) + off
            src = tables[aname]
            if src.ndim == 3:  # fused cold residual: row gather off the lines
                from tdfo_tpu.ops.pallas_kernels import fat_gather_rows

                hot = fat_gather_rows(src, idx, self.fat_layout_for(aname))
            else:
                hot = jnp.take(src, idx, axis=0)
                if self.array_is_int8(aname):
                    # head stays f32: decode the gathered rows on the parent
                    # grid so the initial effective table is bit-identical
                    # to the non-split int8 run
                    hot = dequantize_rows(
                        hot, jnp.take(tables[qscale_name(aname)], idx, axis=0))
            if self.mesh is not None:
                hot = jax.device_put(hot, NamedSharding(self.mesh, P()))
            tables[self.hot_array_name(tname)] = hot
        return tables

    # -------------------------------------------------------- update cache

    def cached_array_names(self, opt, tables) -> tuple[str, ...]:
        """Array names the update cache covers (sorted): plain 2D arrays
        that actually receive row-sparse updates.  Excluded: fat 3D arrays
        (their in-place DMA kernel is already the scatter answer), hot
        HEADS and full-hot cold arrays (dense/never updated), and
        small-vocab adam arrays (``dense_lazy_adam`` is already
        scatter-free)."""
        if self.cache_rows <= 0:
            return ()
        hot_heads = {self.hot_array_name(t) for t in self.hot_ids}
        updated = set()
        for tname in self.specs:
            if self._hot_full.get(tname, False):
                continue  # cold side is dead storage, never updated
            aname, _, _ = self.resolve_table(tname)
            updated.add(aname)
        out = []
        for aname in sorted(updated):
            t = tables[aname]
            if t.ndim != 2 or aname in hot_heads:
                continue
            if (opt.kind == "adam" and t.shape[0] <= opt.small_vocab_threshold
                    and not self.array_is_int8(aname)):
                # the scatter-free dense_lazy_adam tier covers f32/bf16 only;
                # int8 small-vocab adam arrays stay row-sparse, so the cache
                # DOES cover them
                continue
            out.append(aname)
        return tuple(out)

    def init_caches(self, tables, opt) -> dict[str, dict]:
        """Fresh (empty) update caches for every cached array, keyed
        ``CACHE_PREFIX + array_name`` — merged into ``state.slots`` by the
        trainer so checkpoint/rollback/donation cover the cache for free.
        Caches are replicated (P()): C is small and every device routes the
        full id stream through the directory."""
        out: dict[str, dict] = {}
        for aname in self.cached_array_names(opt, tables):
            cache = opt.cache_init(tables[aname], self.cache_rows)
            if self.mesh is not None:
                cache = jax.device_put(
                    cache, NamedSharding(self.mesh, P()))
            out[CACHE_PREFIX + aname] = cache
        return out

    # -------------------------------------------------------------- lookup

    def features(self) -> tuple[str, ...]:
        """All feature names served by this collection (public contract for
        train steps that split sparse/dense params)."""
        return tuple(self._feature_to_table)

    def resolve(self, feature: str) -> tuple[str, EmbeddingSpec, int]:
        """Map a feature name to ``(array_name, spec, row_offset)``.

        ``array_name`` is the key into the ``init()`` pytree (a stacked group
        array for table-wise specs) and ``row_offset`` the feature's base row
        within it.  Public API: the sparse-optimizer step and checkpoint
        tooling depend on it.
        """
        tname = self._feature_to_table.get(feature)
        if tname is None:
            raise KeyError(f"no table serves feature {feature!r}")
        return self.resolve_table(tname)

    def resolve_table(self, tname: str) -> tuple[str, EmbeddingSpec, int]:
        """:meth:`resolve` keyed by logical TABLE name instead of feature."""
        spec = self.specs[tname]
        if spec.sharding == "table":
            offset, _ = self._stack_rows[tname]
            return f"__stack_{spec.embedding_dim}", spec, offset
        gname = self._fat_member_to_stack.get(tname)
        if gname is not None:
            offset, _ = self._stack_rows[tname]
            return gname, spec, offset
        return tname, spec, 0

    # backward-compat alias; prefer resolve()
    _resolve = resolve

    def array_embedding_dim(self, array_name: str) -> int:
        """Embedding dim of an ``init()`` pytree entry (stacked groups carry
        it in their name; fat arrays don't expose it in their shape)."""
        if array_name in self._fat_groups:  # fat AND plain table stacks
            return self._fat_groups[array_name][1]
        if array_name.startswith("__stack_"):
            return int(array_name.removeprefix("__stack_"))
        return self.specs[array_name].embedding_dim

    def array_is_int8(self, array_name: str) -> bool:
        """True when an ``init()`` array stores int8 codes (its f32
        (scale, offset) sidecar lives at ``qscale_name(array_name)``)."""
        return jnp.dtype(self._array_rep_spec(array_name).dtype) == jnp.int8

    def needs_shard_map_update(self, array_name: str) -> bool:
        """True when the array's sparse update must run inside an explicit
        ``shard_map`` (fused fat storage + real row sharding: Pallas has no
        GSPMD partitioning rule).  Public so the dedup-lookup step can route
        such arrays through :meth:`sparse_update` and everything else through
        the shared-dedupe ``update_unique`` fast path."""
        if array_name in self._fat_groups:
            shard_kind = self._fat_groups[array_name][0]
            fused = array_name.startswith("__fatstack_")
            row_sharded = shard_kind == "row"
        elif array_name.startswith("__stack_"):
            fused, row_sharded = False, True
        else:
            spec = self.specs[array_name]
            fused, row_sharded = spec.fused, spec.sharding == "row"
        return (fused and row_sharded
                and self.mesh is not None and self.n_shards > 1)

    def sparse_update(self, opt, array_name: str, table, slots, ids, grads,
                      max_distinct: int | None = None, sr_key=None,
                      qscale=None):
        """Apply the row-sparse optimizer to one table, sharding-aware.

        For fused (fat-row) tables ROW-SHARDED over a real model axis the
        update runs inside an explicit ``shard_map``: Pallas calls have no
        GSPMD partitioning rule, so a plain jit would all-gather the whole
        [V, T, 128] fat table onto every device — the opposite of the
        O(touched-rows) property.  The program: all-gather (ids, grads) over
        the data axis, mask to locally-owned rows, dedupe, in-place kernel on
        the local shard.  Every data-axis replica computes its model shard's
        update identically, so the result stays consistent and sharded.
        Everything else routes straight to ``opt.update``.

        ``sr_key``: stochastic-rounding key for narrow-storage tables
        (``ops/quant.sr_key``); ``None`` leaves the f32 call graph
        untouched.  Inside the shard_map the key is folded with the MODEL
        axis index so shards draw independent rounding bits, while data-
        axis replicas (which recompute the same shard update) stay
        identical.
        """
        d = self.array_embedding_dim(array_name)
        if not self.needs_shard_map_update(array_name):
            return opt.update(table, slots, ids, grads, embedding_dim=d,
                              capacity=max_distinct, max_distinct=max_distinct,
                              sr_key=sr_key, qscale=qscale,
                              platform=self.platform)
        if qscale is not None:
            raise ValueError(
                f"array {array_name!r}: fat-line int8 tables carry their "
                "(scale, offset) sidecar in-line — qscale is only for plain "
                "2D int8 tables")

        from tdfo_tpu.core.mesh import DATA_AXIS
        from tdfo_tpu.ops.sparse import fat_update

        axis = self.axis
        kind = self.fused_kind
        # table.shape[0] counts LINES; shards own whole lines (init rounds
        # rows to n_shards x R), so each shard covers lines x R vocab rows
        rows_per_shard = (table.shape[0] // self.n_shards
                          ) * self.fat_layout(d, table.dtype).r
        ids_flat = ids.reshape(-1)
        grads_flat = grads.reshape(-1, grads.shape[-1])

        def local(fat_shard, slots_in, ids_local, grads_local, *key_in):
            ids_all = jax.lax.all_gather(ids_local, DATA_AXIS, tiled=True)
            g_all = jax.lax.all_gather(grads_local, DATA_AXIS, tiled=True)
            k = jax.lax.axis_index(axis)
            local_ids = ids_all - k * rows_per_shard
            mine = (local_ids >= 0) & (local_ids < rows_per_shard)
            # foreign rows become negative -> dedupe maps them to the
            # dropped sentinel; their (zeroed) grads contribute nothing
            masked = jnp.where(mine, local_ids, -1)
            g_masked = jnp.where(mine[:, None], g_all, 0.0)
            sk = (jax.random.fold_in(key_in[0], k) if key_in else None)
            return fat_update(
                fat_shard, slots_in, masked, g_masked, embedding_dim=d,
                kind=kind, lr=opt.lr, b1=opt.b1, b2=opt.b2, eps=opt.eps,
                weight_decay=opt.weight_decay,
                capacity=max_distinct, max_distinct=max_distinct,
                sr_key=sk, platform=self.platform,
            )

        mesh = self.mesh
        fat_spec = P(axis, None, None)
        slots_spec = tuple(P() for _ in slots)
        key_ops = () if sr_key is None else (sr_key,)
        new_table, new_slots = shard_map(
            local,
            mesh=mesh,
            in_specs=(fat_spec, slots_spec, P(DATA_AXIS), P(DATA_AXIS, None),
                      *(P() for _ in key_ops)),
            out_specs=(fat_spec, slots_spec),
            check_vma=False,
        )(table, slots, ids_flat, grads_flat, *key_ops)
        return new_table, new_slots

    def a2a_overflow(self, tables: Mapping[str, jax.Array],
                     features: Mapping[str, jax.Array]) -> jax.Array:
        """TOTAL ids this batch that the ``alltoall`` lookup program drops
        under a finite ``a2a_capacity_factor`` (they resolve to ZERO
        vectors — the knob's failure mode, torchrec-planner capacity
        semantics).  A silent quality degradation unless watched: the
        Trainer folds this counter into its JSONL log at every log
        boundary in the alltoall regime.  Cheap diagnostic — owner
        bucketing arithmetic only, no table reads and no collectives
        beyond one psum; returns a global (replicated) int32 scalar.
        """
        if (self.a2a_capacity_factor is None or self.mesh is None
                or self.n_shards <= 1):
            return jnp.zeros((), jnp.int32)
        m = self.n_shards
        axis = self.axis
        cf = self.a2a_capacity_factor
        total = jnp.zeros((), jnp.int32)
        if self.grouped_a2a:
            # grouped mode: ONE capacity over each group's combined stream
            # (the cap the real exchange uses), not per-table caps
            eligible = {
                f: ids for f, ids in features.items()
                if (self._feature_to_table.get(f, f) not in self.hot_ids
                    and self.resolve(f)[1].sharding in ("row", "table"))
            }
            for g in self._grouped_plan(tuple(eligible)):
                flats = self._group_flats(g, eligible)
                feat_rps = self._group_feat_rps(g)

                def local(*id_parts, _feat_rps=feat_rps):
                    owner, _ = self._owner_virt(id_parts, _feat_rps)
                    n = owner.shape[0]
                    cap = _a2a_bucket_cap(n, m, cf)
                    counts = jnp.sum(
                        owner[None, :] == jnp.arange(m)[:, None], axis=1)
                    dropped = jnp.sum(jnp.maximum(counts - cap, 0))
                    return jax.lax.psum(dropped.astype(jnp.int32), axis)

                cnt = shard_map(
                    local, mesh=self.mesh,
                    in_specs=tuple(P(axis) for _ in flats), out_specs=P(),
                    check_vma=False,
                )(*flats)
                total = total + cnt
            return total
        for feat, ids in features.items():
            tname, spec, offset = self.resolve(feat)
            if spec.sharding not in ("row", "table"):
                continue
            rows_per_shard = self._rows_per_shard(tables[tname], spec)

            def local(ids_local, rows_per_shard=rows_per_shard, offset=offset):
                flat = ids_local.reshape(-1) + offset
                n = flat.shape[0]
                cap = _a2a_bucket_cap(n, m, cf)
                owner = jnp.clip(flat // rows_per_shard, 0, m - 1)
                counts = jnp.sum(
                    (owner[None, :] == jnp.arange(m)[:, None]), axis=1
                )
                dropped = jnp.sum(jnp.maximum(counts - cap, 0))
                return jax.lax.psum(dropped.astype(jnp.int32), axis)

            cnt = shard_map(
                local, mesh=self.mesh,
                in_specs=P(axis, *([None] * (ids.ndim - 1))), out_specs=P(),
                check_vma=False,
            )(ids)
            total = total + cnt
        return total

    def a2a_fill_stats(self, tables: Mapping[str, jax.Array],
                       features: Mapping[str, jax.Array]):
        """Send-bucket utilisation of the ``alltoall`` lookup program for
        this batch: ``(fill, dropped)`` where ``fill`` is the f32 fraction
        of total bucket capacity actually carrying ids and ``dropped`` the
        int32 overflow count (:meth:`a2a_overflow` semantics).  The
        telemetry companion of the capacity knob: a LOW fill says the
        factor can shrink (smaller a2a payloads), overflow > 0 says it
        already dropped ids.  Same cost shape as ``a2a_overflow`` — owner
        bucketing arithmetic + one psum per group, no table reads.  The
        bodies stay counter-free (``core/mesh.shard_map`` suppresses
        emission); callers emit the returned values."""
        if self.mesh is None or self.n_shards <= 1:
            return jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)
        m = self.n_shards
        axis = self.axis
        cf = self.a2a_capacity_factor
        sent = jnp.zeros((), jnp.int32)
        cap_total = jnp.zeros((), jnp.int32)
        dropped = jnp.zeros((), jnp.int32)

        def bucket_stats(owner, n):
            cap = _a2a_bucket_cap(n, m, cf)
            counts = jnp.sum(owner[None, :] == jnp.arange(m)[:, None], axis=1)
            s = jnp.sum(jnp.minimum(counts, cap))
            d = jnp.sum(jnp.maximum(counts - cap, 0))
            return (jax.lax.psum(s.astype(jnp.int32), axis),
                    jax.lax.psum(jnp.asarray(m * cap, jnp.int32), axis),
                    jax.lax.psum(d.astype(jnp.int32), axis))

        if self.grouped_a2a:
            eligible = {
                f: ids for f, ids in features.items()
                if (self._feature_to_table.get(f, f) not in self.hot_ids
                    and self.resolve(f)[1].sharding in ("row", "table"))
            }
            for g in self._grouped_plan(tuple(eligible)):
                flats = self._group_flats(g, eligible)
                feat_rps = self._group_feat_rps(g)

                def local(*id_parts, _feat_rps=feat_rps):
                    owner, _ = self._owner_virt(id_parts, _feat_rps)
                    return bucket_stats(owner, owner.shape[0])

                s, c, d = shard_map(
                    local, mesh=self.mesh,
                    in_specs=tuple(P(axis) for _ in flats),
                    out_specs=(P(), P(), P()), check_vma=False,
                )(*flats)
                sent, cap_total, dropped = sent + s, cap_total + c, dropped + d
        else:
            for feat, ids in features.items():
                tname, spec, offset = self.resolve(feat)
                if spec.sharding not in ("row", "table"):
                    continue
                rows_per_shard = self._rows_per_shard(tables[tname], spec)

                def local(ids_local, rows_per_shard=rows_per_shard,
                          offset=offset):
                    flat = ids_local.reshape(-1) + offset
                    owner = jnp.clip(flat // rows_per_shard, 0, m - 1)
                    return bucket_stats(owner, flat.shape[0])

                s, c, d = shard_map(
                    local, mesh=self.mesh,
                    in_specs=P(axis, *([None] * (ids.ndim - 1))),
                    out_specs=(P(), P(), P()), check_vma=False,
                )(ids)
                sent, cap_total, dropped = sent + s, cap_total + c, dropped + d
        fill = sent.astype(jnp.float32) / jnp.maximum(
            cap_total.astype(jnp.float32), 1.0)
        return fill, dropped

    def lookup(
        self,
        tables: Mapping[str, jax.Array],
        features: Mapping[str, jax.Array],
        mode: str = "gspmd",
    ) -> dict[str, jax.Array]:
        """ids -> vectors for every feature.  ids may be any shape; output
        gains a trailing ``embedding_dim`` axis."""
        out: dict[str, jax.Array] = {}
        if (mode == "alltoall" and self.grouped_a2a and self.mesh is not None
                and self.n_shards > 1):
            # grouped exchange covers every row/table-sharded feature; the
            # rest (replicated tables, and the error paths) fall through to
            # the per-feature logic below unchanged
            grouped = {
                f: ids for f, ids in features.items()
                if (self._feature_to_table.get(f, f) not in self.hot_ids
                    and self.resolve(f)[1].sharding in ("row", "table"))
            }
            if grouped:
                out.update(self.grouped_lookup(tables, grouped))
                features = {f: i for f, i in features.items()
                            if f not in grouped}
        for feat, ids in features.items():
            if self._feature_to_table.get(feat) in self.hot_ids:
                out[feat] = self._lookup_hotcold(tables, feat, ids, mode)
                continue
            tname, spec, offset = self.resolve(feat)
            table = tables[tname]
            if mode == "gspmd" or self.mesh is None or spec.sharding in ("replicated",):
                if spec.fused:
                    # gather FULL packed lines off the 3D array (one fast
                    # 512B descriptor per id — reshaping the table to a row
                    # view would materialise a multi-GB copy under TPU
                    # tiled layouts), then slot-select the table lanes on
                    # the small gathered block.
                    from tdfo_tpu.ops.pallas_kernels import fat_gather_rows

                    vecs = fat_gather_rows(
                        table, ids + offset,
                        self.fat_layout(spec.embedding_dim, spec.dtype),
                    )
                else:
                    vecs = jnp.take(table, ids + offset, axis=0)
                    if _spec_is_int8(spec):
                        # sidecar rides the same gather; dequantize the SMALL
                        # gathered block, never the table
                        vecs = dequantize_rows(vecs, jnp.take(
                            tables[qscale_name(tname)], ids + offset, axis=0))
                if self.mesh is not None and spec.sharding == "column":
                    vecs = jax.lax.with_sharding_constraint(
                        vecs, NamedSharding(self.mesh, P(*([None] * ids.ndim), self.axis))
                    )
            elif mode in ("psum", "alltoall"):
                # explicit-collective programs assume row-contiguous shards;
                # column-sharded tables would silently reshard every step.
                if spec.sharding not in ("row", "table"):
                    raise ValueError(
                        f"lookup mode {mode!r} requires row/table sharding, "
                        f"but table {spec.name!r} is {spec.sharding!r}"
                    )
                # fused int8 decodes inside the line gather (sidecar rides
                # in-line), so only plain 2D int8 ships a qscale operand
                qs = (tables[qscale_name(tname)]
                      if _spec_is_int8(spec) and not spec.fused else None)
                if mode == "psum":
                    vecs = self._lookup_psum(table, ids + offset, spec, qs)
                else:
                    vecs = self._lookup_alltoall(table, ids + offset, spec, qs)
            else:
                raise ValueError(f"unknown lookup mode {mode!r}")
            out[feat] = vecs
        # reads dequantize after the gather/exchange: activations are f32 at
        # the model interface whatever the storage dtype (identity for f32,
        # including every grouped_lookup output already cast inside)
        return {f: v.astype(jnp.float32) for f, v in out.items()}

    def _lookup_hotcold(self, tables, feat: str, ids: jax.Array, mode: str):
        """Routed lookup for a hot/cold table: gather both sides (row
        gathers are cheap on v5e, ~60-90 us for 8192 x 64), select per
        position.  Fully-hot tables skip the cold gather statically.  The
        dedup-lookup train step re-implements the cold half over its shared
        sort; this method is the plain-forward/eval path."""
        if mode != "gspmd":
            raise ValueError(
                f"hot/cold tables compose with lookup mode 'gspmd' only, "
                f"got {mode!r} for feature {feat!r}")
        tname = self._feature_to_table[feat]
        hot_pos, cold_ids = self.route_ids(feat, ids)
        hot = tables[self.hot_array_name(tname)]
        hot_vec = jnp.take(hot, jnp.maximum(hot_pos, 0), axis=0)
        if self._hot_full[tname]:
            return hot_vec  # padding ids clamp to hot row 0 (clip parity)
        aname, spec, offset = self.resolve(feat)
        cidx = jnp.where(cold_ids >= 0, cold_ids + offset, 0)
        src = tables[aname]
        if src.ndim == 3:  # fused cold residual (incl. int8 byte lines)
            from tdfo_tpu.ops.pallas_kernels import fat_gather_rows

            cold_vec = fat_gather_rows(src, cidx, self.fat_layout_for(aname))
        else:
            cold_vec = jnp.take(src, cidx, axis=0)
            if _spec_is_int8(spec):
                # int8 cold residual: decode the SMALL gathered block (the
                # head is f32, so the select below mixes f32 both sides)
                cold_vec = dequantize_rows(
                    cold_vec, jnp.take(tables[qscale_name(aname)], cidx,
                                       axis=0))
        return jnp.where((hot_pos >= 0)[..., None],
                         hot_vec.astype(cold_vec.dtype), cold_vec)

    def _local_gather(self, spec: EmbeddingSpec):
        """(table_shard, vocab-row idx) -> [.., d] gather for the explicit
        collective programs, fused-aware: packed shards line-gather +
        slot-select the table lanes BEFORE the collective (also shrinks the
        bytes on the wire 2-8x vs shipping whole lines)."""
        if not spec.fused:
            return lambda shard, idx: jnp.take(shard, idx, axis=0)
        from tdfo_tpu.ops.pallas_kernels import fat_gather_rows

        lay = self.fat_layout(spec.embedding_dim, spec.dtype)
        return lambda shard, idx: fat_gather_rows(shard, idx, lay)

    def _rows_per_shard(self, table: jax.Array, spec: EmbeddingSpec) -> int:
        """Vocab rows per model-axis shard (fat shards count lines x R)."""
        mult = (self.fat_layout(spec.embedding_dim, spec.dtype).r
                if spec.fused else 1)
        return (table.shape[0] // self.n_shards) * mult

    # ------------------------------------------------- grouped alltoall

    def _array_vocab_rows(self, array_name: str) -> int:
        """Padded vocab-row count of an ``init()`` array, derived from the
        specs alone (matches ``table.shape`` but needs no live array — the
        grouped input-dist must not carry a data dependency on the tables,
        or pipelining it ahead of the update would be illegal)."""
        if array_name in self._fat_groups:  # fat AND plain table stacks
            _, _, group = self._fat_groups[array_name]
            return self._stack_rows[group[0].name][1]
        if array_name.startswith("__stack_"):
            group = self._groups[array_name]
            return self._stack_rows[group[0].name][1]
        spec = self.specs[array_name]
        unit = (self.fat_layout(spec.embedding_dim, spec.dtype).r
                if spec.fused else 1)
        if spec.sharding == "row":
            unit *= self.n_shards
        return _round_up(spec.num_embeddings, unit)

    def _array_rep_spec(self, array_name: str) -> EmbeddingSpec:
        """A representative member spec of an ``init()`` array (stack
        members share dim/dtype/fused-ness, which is all callers read)."""
        if array_name in self._fat_groups:
            return self._fat_groups[array_name][2][0]
        if array_name.startswith("__stack_"):
            return self._groups[array_name][0]
        return self.specs[array_name]

    def _grouped_plan(self, feature_names: tuple[str, ...]) -> tuple[_A2AGroup, ...]:
        """Static exchange plan for a feature set: one :class:`_A2AGroup`
        per (embedding_dim, dtype) — vectors of one group share a payload
        shape, so the whole group rides one ``all_to_all`` pair.  Feature
        order is preserved (it defines the combined stream's summation
        order, which the update-parity guarantee depends on)."""
        plan = self._grouped_plans.get(feature_names)
        if plan is not None:
            return plan
        groups: dict[tuple[int, str], dict] = {}
        for f in feature_names:
            tname = self._feature_to_table.get(f, f)
            if tname in self.hot_ids:
                raise ValueError(
                    f"feature {f!r}: hot/cold tables do not compose with "
                    "the grouped alltoall exchange")
            aname, spec, off = self.resolve(f)
            if spec.sharding not in ("row", "table"):
                raise ValueError(
                    f"grouped alltoall requires row/table sharding, but "
                    f"table {spec.name!r} is {spec.sharding!r}")
            key = (spec.embedding_dim, jnp.dtype(spec.dtype).name)
            grp = groups.setdefault(key, {"arrays": [], "feats": []})
            if aname not in grp["arrays"]:
                grp["arrays"].append(aname)
            grp["feats"].append((f, grp["arrays"].index(aname), off))
        entries = []
        for (dim, dt), grp in sorted(groups.items(), key=lambda kv: str(kv[0])):
            arrays = tuple(grp["arrays"])
            rps = tuple(self._array_vocab_rows(a) // self.n_shards
                        for a in arrays)
            bases, b = [], 0
            for r in rps:
                bases.append(b)
                b += r
            entries.append(_A2AGroup(
                key=f"{dim}_{dt}", dim=dim,
                feats=tuple(x[0] for x in grp["feats"]),
                feat_meta=tuple((x[1], x[2]) for x in grp["feats"]),
                arrays=arrays,
                specs=tuple(self._array_rep_spec(a) for a in arrays),
                rows_per_shard=rps, bases=tuple(bases)))
        plan = tuple(entries)
        self._grouped_plans[feature_names] = plan
        return plan

    def _owner_virt(self, id_parts, feat_meta_rps):
        """Combined (owner, virtual id) stream of a group, inside shard_map.

        Negative (padding) ids keep a virtual id of -1 — they bucket to
        shard 0 like the per-table program, arrive as invalid, and resolve
        to zero vectors / dropped grads regardless of which array's base
        range -1+base would otherwise fall into."""
        m = self.n_shards
        owners, virts = [], []
        for part, (rps, base) in zip(id_parts, feat_meta_rps):
            o = jnp.clip(part // rps, 0, m - 1)
            owners.append(o)
            virts.append(jnp.where(part >= 0, part - o * rps + base, -1))
        owner = jnp.concatenate(owners) if len(owners) > 1 else owners[0]
        virt = jnp.concatenate(virts) if len(virts) > 1 else virts[0]
        return owner, virt

    def _group_flats(self, group: _A2AGroup, features) -> tuple:
        """Per-feature flattened offset-shifted int32 id streams.  Padding
        ids stay -1 — an unconditional ``+ off`` would alias them onto the
        last row of the preceding stack member (``off - 1``), breaking the
        :meth:`_owner_virt` sentinel contract for stacked tables."""
        out = []
        for f, (_, off) in zip(group.feats, group.feat_meta):
            flat = features[f].reshape(-1)
            out.append(jnp.where(flat >= 0, flat + off, -1).astype(jnp.int32))
        return tuple(out)

    def _group_feat_rps(self, group: _A2AGroup) -> tuple:
        """Per-feature (rows_per_shard, base) of the feature's array."""
        return tuple((group.rows_per_shard[ai], group.bases[ai])
                     for ai, _ in group.feat_meta)

    def grouped_input_dist(self, features: Mapping[str, jax.Array]) -> dict:
        """Phase 1 of the grouped alltoall program (torchrec KJTAllToAll
        input-dist parity): ONE stable owner sort + ONE id ``all_to_all``
        over each group's combined virtual id stream.  Reads NO tables —
        the returned ctx (per group: received id buckets + the unpermute
        map) is a plain pytree that :meth:`grouped_lookup` completes, and
        the train pipeline may compute it for batch N+1 before batch N's
        update.  The owner sort is STABLE so the received stream preserves
        global batch order — the property that makes :meth:`grouped_update`
        bit-identical to the per-table path — and so forward/backward drop
        the SAME overflowed ids under a finite capacity factor."""
        plan = self._grouped_plan(tuple(features))
        m = self.n_shards
        axis = self.axis
        cf = self.a2a_capacity_factor
        ctx = {}
        for g in plan:
            flats = self._group_flats(g, features)
            feat_rps = self._group_feat_rps(g)

            def dist(*id_parts, _feat_rps=feat_rps):
                owner, virt = self._owner_virt(id_parts, _feat_rps)
                n = owner.shape[0]
                cap = _a2a_bucket_cap(n, m, cf)
                iota = jnp.arange(n, dtype=jnp.int32)
                sorted_owner, sorted_virt, order = jax.lax.sort(
                    (owner, virt, iota), num_keys=1, is_stable=True)
                bucket_start = jnp.searchsorted(
                    sorted_owner, jnp.arange(m), method="sort")
                src = bucket_start[:, None] + jnp.arange(cap)[None, :]
                bucket_end = jnp.append(bucket_start[1:], n)
                in_bucket = src < bucket_end[:, None]
                send = jnp.where(
                    in_bucket, jnp.take(sorted_virt, jnp.minimum(src, n - 1)),
                    -1)
                recv = jax.lax.all_to_all(
                    send, axis, split_axis=0, concat_axis=0)
                pos = iota - jnp.take(bucket_start, sorted_owner)
                slot = jnp.where(pos < cap, sorted_owner * cap + pos, -1)
                _, slot_inv = jax.lax.sort(
                    (order, slot), num_keys=1, is_stable=False)
                return recv, slot_inv

            recv, slot_inv = shard_map(
                dist, mesh=self.mesh,
                in_specs=tuple(P(axis) for _ in flats),
                out_specs=(P(axis, None), P(axis)),
                check_vma=False,
            )(*flats)
            ctx[g.key] = (recv, slot_inv)
        return ctx

    def grouped_lookup(
        self,
        tables: Mapping[str, jax.Array],
        features: Mapping[str, jax.Array],
        ctx: dict | None = None,
    ) -> dict[str, jax.Array]:
        """Grouped alltoall lookup: complete a :meth:`grouped_input_dist`
        ctx (or run it inline) with the owners' gathers and ONE vector
        ``all_to_all`` per group — 2 collectives per group per step total,
        vs 2 per TABLE in the per-table program.  Per-feature outputs are
        split inside the shard_map local function (each shard's block
        concatenates its feature slices locally, so slicing the logical
        concat outside would interleave shards wrongly)."""
        plan = self._grouped_plan(tuple(features))
        if ctx is None:
            ctx = self.grouped_input_dist(features)
        m = self.n_shards
        axis = self.axis
        out: dict[str, jax.Array] = {}
        for g in plan:
            recv, slot_inv = ctx[g.key]
            shards = tuple(tables[a] for a in g.arrays)
            # groups are dtype-uniform ((dim, dtype) keys), so one flag
            # covers every member array.  Only plain 2D int8 arrays carry a
            # separate sidecar — fused int8 lines decode inside the line
            # gather, so they take no qscale operand.
            is_int8 = jnp.dtype(g.specs[0].dtype) == jnp.int8
            qs_arrays = tuple(a for a, s in zip(g.arrays, g.specs)
                              if is_int8 and not s.fused)
            qshards = tuple(tables[qscale_name(a)] for a in qs_arrays)
            qs_pos = {a: i for i, a in enumerate(qs_arrays)}
            gathers = tuple(self._local_gather(s) for s in g.specs)
            local_sizes = tuple(features[f].size // m for f in g.feats)

            def complete(recv_l, slot_inv_l, *ops, _g=g,
                         _gathers=gathers, _sizes=local_sizes,
                         _qs_pos=qs_pos):
                shards_l = ops[:len(_g.arrays)]
                qs_l = ops[len(_g.arrays):]
                flatr = recv_l.reshape(-1)  # [m * cap]
                valid = flatr >= 0
                vec, qvec = None, None
                # per-array masked gathers; base ranges are disjoint, so the
                # sum of masked rows IS the select across arrays (int8: at
                # most one term per slot is nonzero, so the int8 adds never
                # overflow)
                for ai, (shard, gather, rps, base) in enumerate(zip(
                        shards_l, _gathers, _g.rows_per_shard, _g.bases)):
                    loc = flatr - base
                    mine = valid & (loc >= 0) & (loc < rps)
                    clipped = jnp.clip(loc, 0, rps - 1)
                    rows = gather(shard, clipped)
                    rows = jnp.where(mine[:, None], rows, 0)
                    vec = rows if vec is None else vec + rows
                    if qs_l:
                        qi = _qs_pos.get(_g.arrays[ai])
                        if qi is None:
                            # fused int8 member of a mixed group: its rows
                            # arrive DECODED, so its slots decode again on
                            # the identity grid (scale 1, offset 0)
                            qrows = jnp.where(
                                mine[:, None],
                                jnp.array([1.0, 0.0], jnp.float32)[None, :],
                                0.0)
                        else:
                            qrows = jnp.where(
                                mine[:, None],
                                jnp.take(qs_l[qi], clipped, axis=0), 0)
                        qvec = qrows if qvec is None else qvec + qrows
                back = jax.lax.all_to_all(
                    vec.reshape(m, -1, vec.shape[-1]), axis,
                    split_axis=0, concat_axis=0)
                # dequantize AFTER the exchange: the vector all_to_all
                # payload rides at storage dtype (half the bytes for bf16,
                # a QUARTER for int8 — the codes ship as int8 and the f32
                # (scale, offset) rows ride a separate small collective);
                # the model always sees f32 activations (identity for f32)
                flat = back.reshape(-1, vec.shape[-1])
                if qs_l:
                    qback = jax.lax.all_to_all(
                        qvec.reshape(m, -1, 2), axis,
                        split_axis=0, concat_axis=0)
                    flat = dequantize_rows(flat, qback.reshape(-1, 2))
                else:
                    flat = flat.astype(jnp.float32)
                outv = jnp.where(
                    (slot_inv_l >= 0)[:, None],
                    jnp.take(flat, jnp.maximum(slot_inv_l, 0), axis=0), 0)
                parts, o = [], 0
                for nloc in _sizes:
                    parts.append(outv[o:o + nloc])
                    o += nloc
                return tuple(parts)

            parts = shard_map(
                complete, mesh=self.mesh,
                in_specs=(P(axis, None), P(axis),
                          *(P(axis, *([None] * (t.ndim - 1)))
                            for t in shards),
                          *(P(axis, None) for _ in qshards)),
                out_specs=tuple(P(axis) for _ in g.feats),
                check_vma=False,
            )(recv, slot_inv, *shards, *qshards)
            for f, p in zip(g.feats, parts):
                out[f] = p.reshape(*features[f].shape, -1)
        return out

    def _grouped_slot_specs(self, table: jax.Array, slots) -> tuple:
        """shard_map partition specs for one array's optimizer slots:
        vocab-aligned state ([V, D] accum/mu/nu, [V] rowwise accum) shards
        with the table; scalars (adam count, fat-table count) replicate."""
        return tuple(
            P(self.axis, *([None] * (leaf.ndim - 1)))
            if (table.ndim == 2 and leaf.ndim >= 1
                and leaf.shape[0] == table.shape[0])
            else P()
            for leaf in slots)

    def grouped_update(self, opt, tables, slots, ids, grads, sr_key=None):
        """The backward half of the grouped exchange: ship each group's
        (virtual id, grad) stream to the owner shards with ONE id + ONE
        grad ``all_to_all``, then dedupe + apply the sparse optimizer on
        each local shard — replacing one ``opt.update`` (and its implied
        GSPMD collectives) per table array.

        Exactness vs the per-table path: the stable owner sort delivers
        each shard its owned contributions in global stream order — the
        same order ``dedupe_grads``' segment-sum adds them in ``opt.update``
        — so per-row grad sums and optimizer outputs are the same
        operations in the same order (single-feature tables; see
        ``__init__``).  Compiled as a different program than an eager
        per-table ``opt.update``, touched rows agree to a few f32 ULP, not
        to the bit; untouched rows are bit-identical
        (``tests/test_grouped_a2a.py``).  Small-vocab adam tables take
        the dedupe tier here rather than ``opt.update``'s one-hot tier
        (a different summation ORDER, same semantics).  Under a finite
        capacity factor, overflowed ids' grads are dropped — the exact ids
        whose forward vectors were zeroed.

        ``ids``/``grads`` map feature name -> raw ids / [..., D] grads.
        Returns ``(new_tables, new_slots)`` dicts covering the plan's
        arrays only.

        ``sr_key``: base stochastic-rounding key for the step (narrow
        storage only; ``None`` keeps the f32 call graph unchanged).  Each
        array folds its stable ``quant.table_id`` plus the model-axis
        index, so no two arrays — and no two shards — share rounding
        bits."""
        from tdfo_tpu.ops.quant import table_id
        from tdfo_tpu.ops.sparse import dedupe_grads, fat_update

        plan = self._grouped_plan(tuple(ids))
        m = self.n_shards
        axis = self.axis
        cf = self.a2a_capacity_factor
        ceil8 = lambda x: -(-x // 8) * 8
        new_tables: dict[str, jax.Array] = {}
        new_slots: dict[str, tuple] = {}
        for g in plan:
            flats = self._group_flats(g, ids)
            gflats = tuple(grads[f].reshape(-1, grads[f].shape[-1])
                           for f in g.feats)
            feat_rps = self._group_feat_rps(g)
            tabs = tuple(tables[a] for a in g.arrays)
            slot_in = tuple(slots[a] for a in g.arrays)
            is_int8 = jnp.dtype(g.specs[0].dtype) == jnp.int8
            # plain 2D int8 arrays carry a separate (scale, offset) sidecar;
            # fused int8 lines pack it in-line and take no qscale operand
            qs_arrays = tuple(a for a, s in zip(g.arrays, g.specs)
                              if is_int8 and not s.fused)
            qs_in = tuple(tables[qscale_name(a)] for a in qs_arrays)
            qs_pos = {a: i for i, a in enumerate(qs_arrays)}
            n_local = sum(f.shape[0] for f in flats) // m
            cap = _a2a_bucket_cap(n_local, m, cf)
            stream = m * cap
            # per-array distinct bound: a shard can't touch more rows (fat:
            # lines) than it owns, +1 for the dedupe sentinel slot
            mds = []
            for spec, rps in zip(g.specs, g.rows_per_shard):
                # int8 fat lines dedupe in ROW space (per-row requantize),
                # so their distinct bound counts rows, not lines
                unit = (self.fat_layout(g.dim, spec.dtype).r
                        if spec.fused
                        and jnp.dtype(spec.dtype) != jnp.int8 else 1)
                mds.append(min(stream, ceil8(rps // unit + 1)))
            mds = tuple(mds)

            def local_upd(tabs_l, slots_l, qs_tl, *parts, _g=g,
                          _feat_rps=feat_rps, _mds=mds, _cap=cap,
                          _qs_pos=qs_pos):
                k = len(_g.feats)
                key_l = parts[2 * k] if len(parts) > 2 * k else None
                g_parts = parts[k:2 * k]
                owner, virt = self._owner_virt(parts[:k], _feat_rps)
                gcat = (jnp.concatenate(g_parts) if k > 1 else g_parts[0])
                n = owner.shape[0]
                iota = jnp.arange(n, dtype=jnp.int32)
                sorted_owner, sorted_virt, order = jax.lax.sort(
                    (owner, virt, iota), num_keys=1, is_stable=True)
                g_sorted = jnp.take(gcat, order, axis=0)
                bucket_start = jnp.searchsorted(
                    sorted_owner, jnp.arange(m), method="sort")
                src = bucket_start[:, None] + jnp.arange(_cap)[None, :]
                bucket_end = jnp.append(bucket_start[1:], n)
                in_bucket = src < bucket_end[:, None]
                safe = jnp.minimum(src, n - 1)
                send_ids = jnp.where(
                    in_bucket, jnp.take(sorted_virt, safe), -1)
                send_g = jnp.where(
                    in_bucket[..., None], jnp.take(g_sorted, safe, axis=0), 0)
                recv_ids = jax.lax.all_to_all(
                    send_ids, axis, split_axis=0, concat_axis=0).reshape(-1)
                recv_g = jax.lax.all_to_all(
                    send_g, axis, split_axis=0, concat_axis=0
                ).reshape(-1, gcat.shape[-1])
                out_t, out_s, out_q = [], [], []
                for ai, (aname, shard, sl, spec, rps, base, md) in enumerate(
                        zip(_g.arrays, tabs_l, slots_l, _g.specs,
                            _g.rows_per_shard, _g.bases, _mds)):
                    loc = recv_ids - base
                    mine = (recv_ids >= 0) & (loc >= 0) & (loc < rps)
                    mids = jnp.where(mine, loc, -1)
                    mg = jnp.where(mine[:, None], recv_g, 0)
                    sk = None
                    if key_l is not None:
                        sk = jax.random.fold_in(key_l, table_id(aname))
                        sk = jax.random.fold_in(sk, jax.lax.axis_index(axis))
                    if spec.fused:
                        nt, ns = fat_update(
                            shard, sl, mids, mg, embedding_dim=_g.dim,
                            kind=self.fused_kind, lr=opt.lr, b1=opt.b1,
                            b2=opt.b2, eps=opt.eps,
                            weight_decay=opt.weight_decay,
                            capacity=md, max_distinct=md, sr_key=sk,
                            platform=self.platform)
                    else:
                        uids, gu, valid = dedupe_grads(
                            mids, mg, capacity=md, vocab=rps,
                            max_distinct=md)
                        qi = _qs_pos.get(aname)
                        if qi is not None:
                            nt, ns, nq = opt.update_unique(
                                shard, sl, uids, gu, valid,
                                embedding_dim=_g.dim, sr_key=sk,
                                qscale=qs_tl[qi], platform=self.platform)
                            out_q.append(nq)
                        else:
                            nt, ns = opt.update_unique(
                                shard, sl, uids, gu, valid,
                                embedding_dim=_g.dim, sr_key=sk,
                                platform=self.platform)
                    out_t.append(nt)
                    out_s.append(ns)
                return tuple(out_t), tuple(out_s), tuple(out_q)

            tab_specs = tuple(P(axis, *([None] * (t.ndim - 1))) for t in tabs)
            slot_specs = tuple(self._grouped_slot_specs(t, sl)
                               for t, sl in zip(tabs, slot_in))
            qs_specs = tuple(P(axis, None) for _ in qs_in)
            key_ops = () if sr_key is None else (sr_key,)
            upd_t, upd_s, upd_q = shard_map(
                local_upd, mesh=self.mesh,
                in_specs=(tab_specs, slot_specs, qs_specs,
                          *(P(axis) for _ in flats),
                          *(P(axis, None) for _ in gflats),
                          *(P() for _ in key_ops)),
                out_specs=(tab_specs, slot_specs, qs_specs),
                check_vma=False,
            )(tabs, slot_in, qs_in, *flats, *gflats, *key_ops)
            for a, nt, ns in zip(g.arrays, upd_t, upd_s):
                new_tables[a] = nt
                new_slots[a] = ns
            for a, nq in zip(qs_arrays, upd_q):
                # updated sidecars ride new_tables under their prefixed key,
                # so the train step's dict merge covers them with no extra
                # call-site plumbing
                new_tables[qscale_name(a)] = nq
        return new_tables, new_slots

    def _lookup_psum(self, table: jax.Array, ids: jax.Array,
                     spec: EmbeddingSpec, qscale: jax.Array | None = None
                     ) -> jax.Array:
        """Explicit row-shard lookup: ids replicated over the model axis.

        Each device gathers rows it owns and zeros the rest; one ``psum``
        over the model axis assembles full vectors.  Batch stays sharded
        over ``data`` untouched.  int8 tables (``qscale`` given) dequantize
        at the OWNER before the psum — codes from different rows live on
        different grids, so summing them across shards would be meaningless.
        """
        mesh = self.mesh
        axis = self.axis
        rows_per_shard = self._rows_per_shard(table, spec)
        gather_rows = self._local_gather(spec)

        def local(table_shard, ids_local, *qs_shard):
            idx = jax.lax.axis_index(axis)
            start = idx * rows_per_shard
            local_ids = ids_local - start
            mine = (local_ids >= 0) & (local_ids < rows_per_shard)
            clipped = jnp.clip(local_ids, 0, rows_per_shard - 1)
            gathered = gather_rows(table_shard, clipped)
            if qs_shard:
                gathered = dequantize_rows(
                    gathered, jnp.take(qs_shard[0], clipped, axis=0))
            gathered = jnp.where(mine[..., None], gathered, 0)
            return jax.lax.psum(gathered, axis)

        from tdfo_tpu.core.mesh import DATA_AXIS

        ids_spec = P(DATA_AXIS, *([None] * (ids.ndim - 1)))
        out_spec = P(DATA_AXIS, *([None] * ids.ndim))
        table_spec = P(axis, *([None] * (table.ndim - 1)))
        qs_ops = () if qscale is None else (qscale,)
        return shard_map(
            local,
            mesh=mesh,
            in_specs=(table_spec, ids_spec, *(P(axis, None) for _ in qs_ops)),
            out_specs=out_spec,
            check_vma=False,
        )(table, ids, *qs_ops)

    def _lookup_alltoall(self, table: jax.Array, ids: jax.Array,
                         spec: EmbeddingSpec, qscale: jax.Array | None = None
                         ) -> jax.Array:
        """torchrec input-dist/output-dist parity: batch AND table sharded
        over the same ``model`` axis.

        Per device: bucket local ids by owner shard (capacity = local batch,
        the worst case), ``all_to_all`` id buckets, gather owned rows,
        ``all_to_all`` vectors back, un-permute.  Two collectives per lookup,
        both riding ICI — the GSPMD-era NCCL a2a plan.  int8 tables
        (``qscale`` given) dequantize at the owner; the narrow-wire payload
        belongs to the grouped program (:meth:`grouped_lookup`).
        """
        if ids.ndim != 1:
            orig_shape = ids.shape
            flat = ids.reshape(-1)
            out = self._lookup_alltoall(table, flat, spec, qscale)
            return out.reshape(*orig_shape, -1)

        mesh = self.mesh
        axis = self.axis
        m = self.n_shards
        rows_per_shard = self._rows_per_shard(table, spec)
        gather_rows = self._local_gather(spec)
        cf = self.a2a_capacity_factor

        def local(table_shard, ids_local, *qs_shard):
            n = ids_local.shape[0]  # local batch
            cap = _a2a_bucket_cap(n, m, cf)
            owner = jnp.clip(ids_local // rows_per_shard, 0, m - 1)  # [n]
            iota = jnp.arange(n, dtype=jnp.int32)
            # ONE payload-carrying sort by owner -> contiguous buckets AND the
            # permutation, with no id gather (1D gathers cost ~60 us each on
            # v5e; extra sort payloads are nearly free).  Unstable is safe:
            # every use below is self-consistent under ANY owner-sorting
            # permutation.  A scatter-built send buffer would cost ~10x.
            sorted_owner, sorted_ids, order = jax.lax.sort(
                (owner, ids_local.astype(jnp.int32), iota), num_keys=1,
                is_stable=False,
            )
            bucket_start = jnp.searchsorted(sorted_owner, jnp.arange(m),
                                            method="sort")  # [m]
            # send[k, c] = (c)-th id owned by shard k, -1 past bucket end
            src = bucket_start[:, None] + jnp.arange(cap)[None, :]  # [m, cap]
            bucket_end = jnp.append(bucket_start[1:], n)
            in_bucket = src < bucket_end[:, None]
            send = jnp.where(
                in_bucket, jnp.take(sorted_ids, jnp.minimum(src, n - 1)), -1
            )
            # a2a: axis 0 is the peer dim
            recv_ids = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0)
            local_idx = recv_ids - jax.lax.axis_index(axis) * rows_per_shard
            valid = recv_ids >= 0
            clipped = jnp.clip(local_idx, 0, rows_per_shard - 1)
            gathered = gather_rows(table_shard, clipped)
            if qs_shard:
                gathered = dequantize_rows(
                    gathered, jnp.take(qs_shard[0], clipped, axis=0))
            gathered = jnp.where(valid[..., None], gathered, 0)
            # send vectors back to requesters
            back = jax.lax.all_to_all(gathered, axis, split_axis=0, concat_axis=0)
            # sorted element j sat at slot (owner_j, j - bucket_start[owner_j]);
            # overflowed slots (pos >= cap, finite capacity only) get slot -1
            # -> zeros.  A second pair-sort carries each slot back to its
            # original position (replacing inverse-argsort + two 1D gathers),
            # so the unpermute pays ONE [n, D] row gather + one sort.
            pos = iota - jnp.take(bucket_start, sorted_owner)
            flat = back.reshape(m * cap, -1)
            slot = jnp.where(pos < cap, sorted_owner * cap + pos, -1)
            _, slot_inv = jax.lax.sort((order, slot), num_keys=1,
                                       is_stable=False)
            return jnp.where(
                (slot_inv >= 0)[:, None],
                jnp.take(flat, jnp.maximum(slot_inv, 0), axis=0), 0,
            )

        table_spec = P(axis, *([None] * (table.ndim - 1)))
        qs_ops = () if qscale is None else (qscale,)
        return shard_map(
            local,
            mesh=mesh,
            in_specs=(table_spec, P(axis), *(P(axis, None) for _ in qs_ops)),
            out_specs=P(axis),
            check_vma=False,
        )(table, ids, *qs_ops)
