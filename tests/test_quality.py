"""Quality-parity convergence floors (the reference's de-facto acceptance
test is converged in-loop metrics on real Goodreads data:
jax-flax/train_dp.py:219-245 eval ROC-AUC, torchrec/train.py:143-144
Recall@K/NDCG@K).  Reduced-scale versions of tools/quality_run.py (whose
full trajectories are committed under docs/quality/): the signal-bearing
synthetic fixtures make the metrics MEAN something — eval AUC must clear
the 0.5 noise floor decisively, and Bert4Rec's post-training ranking must
decisively beat its own pre-training validation floor."""

# (no slow-marker infra in this suite: these run unconditionally)
import json

import pytest

from tdfo_tpu.core.config import read_configs
from tdfo_tpu.data.ctr_preprocessing import run_ctr_preprocessing
from tdfo_tpu.data.seq_preprocessing import run_seq_preprocessing
from tdfo_tpu.data.synthetic import write_synthetic_goodreads
from tdfo_tpu.train.trainer import Trainer


def test_twotower_converges_above_noise_floor(tmp_path):
    d = tmp_path / "gr"
    write_synthetic_goodreads(d, n_users=800, n_books=320,
                              interactions_per_user=(30, 60), seed=5,
                              signal=0.85)
    size_map = run_ctr_preprocessing(d)
    cfg = read_configs(
        None, data_dir=d, model="twotower", model_parallel=True,
        n_epochs=10, learning_rate=3e-3, weight_decay=1e-3, embed_dim=8,
        per_device_train_batch_size=64, per_device_eval_batch_size=64,
        shuffle_buffer_size=20_000, log_every_n_steps=10_000,
        size_map=size_map,
    )
    metrics = Trainer(cfg).fit()
    # pure-noise data pins eval AUC at ~0.5 forever; the themed fixtures
    # support ~0.6+ at this scale (docs/quality: 0.66 at 15 epochs)
    assert metrics["auc"] >= 0.56, metrics


def test_bert4rec_beats_pretrain_ranking_floor(tmp_path):
    d = tmp_path / "gr"
    write_synthetic_goodreads(d, n_users=300, n_books=320,
                              interactions_per_user=(30, 60), seed=7,
                              signal=0.85)
    stats = run_seq_preprocessing(d, max_len=16, sliding_step=8, seed=7)
    cfg = read_configs(
        None, data_dir=d, model="bert4rec", model_parallel=True,
        n_epochs=10, learning_rate=3e-3, embed_dim=32, n_heads=2,
        n_layers=2, max_len=16, sliding_step=8,
        per_device_train_batch_size=32, per_device_eval_batch_size=32,
        shuffle_buffer_size=20_000, log_every_n_steps=10_000,
        size_map={"n_items": stats["n_items"]},
    )
    log_dir = tmp_path / "logs"
    metrics = Trainer(cfg, log_dir=log_dir).fit()
    # the pre-training validation (epoch -1, torchrec/train.py:159 parity)
    # is the untrained floor of the SAME protocol — convergence must beat
    # it decisively, and clear an absolute floor well above it
    pre = None
    for line in open(log_dir / "metrics.jsonl"):
        rec = json.loads(line)
        if rec.get("epoch") == -1 and "Recall@10" in rec:
            pre = rec
    assert pre is not None
    assert metrics["Recall@10"] >= 0.30, metrics
    assert metrics["Recall@10"] >= pre["Recall@10"] + 0.10, (pre, metrics)
    assert metrics["NDCG@10"] >= pre["NDCG@10"] + 0.05, (pre, metrics)


def test_no_default_method_searchsorted_in_hot_code():
    """`jnp.searchsorted`'s DEFAULT method costs ~6x the `method="sort"`
    formulation on TPU (13 serial narrow gathers vs one sort — measured
    0.86 ms vs 0.14 ms for 8192-into-8192, bit-identical results
    downstream; CLAUDE.md).  Every jnp/jax.numpy call site in the
    package must pass method="sort"; plain numpy searchsorted (host-side
    preprocessing/metrics) is exempt."""
    import ast
    from pathlib import Path

    import tdfo_tpu

    offenders = []
    for path in Path(tdfo_tpu.__file__).parent.rglob("*.py"):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "searchsorted"):
                continue
            base = node.func.value
            # jnp.searchsorted / jax.numpy.searchsorted only
            is_jnp = (isinstance(base, ast.Name) and base.id == "jnp") or (
                isinstance(base, ast.Attribute) and base.attr == "numpy"
                and isinstance(base.value, ast.Name) and base.value.id == "jax")
            if not is_jnp:
                continue
            kw = {k.arg: k.value for k in node.keywords}
            ok = ("method" in kw
                  and isinstance(kw["method"], ast.Constant)
                  and kw["method"].value == "sort")
            if not ok:
                offenders.append(f"{path}:{node.lineno}")
    assert not offenders, (
        "jnp.searchsorted without method='sort' (TPU-hostile default): "
        + ", ".join(offenders))


def test_no_jnp_unique_in_device_code():
    """`jnp.unique(size=...)` costs ~0.2 ms at 8k ids / ~0.5 ms at 16k on
    v5e; the pair-sort + first-mask-cumsum + back-sort formulation
    (`dedupe_grads`/`dedupe_ids`) does the same job in ~0.24 ms at 16k with
    2 sorts + 1 small scatter (CLAUDE.md).  Device-side dedupe in the
    hot paths (`ops/`, `parallel/`) must use it — `jnp.unique` creeping
    back in is a silent multi-x regression.  Host-side numpy unique
    (preprocessing, metrics, tests) is exempt."""
    import ast
    from pathlib import Path

    import tdfo_tpu

    root = Path(tdfo_tpu.__file__).parent
    offenders = []
    for sub in ("ops", "parallel"):
        for path in (root / sub).rglob("*.py"):
            tree = ast.parse(path.read_text(), filename=str(path))
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "unique"):
                    continue
                base = node.func.value
                # jnp.unique / jax.numpy.unique only (np.unique is host-side)
                is_jnp = (isinstance(base, ast.Name) and base.id == "jnp") or (
                    isinstance(base, ast.Attribute) and base.attr == "numpy"
                    and isinstance(base.value, ast.Name)
                    and base.value.id == "jax")
                if is_jnp:
                    offenders.append(f"{path}:{node.lineno}")
    assert not offenders, (
        "jnp.unique in device-side hot-path code (use the dedupe_grads/"
        "dedupe_ids sort formulation — see CLAUDE.md): "
        + ", ".join(offenders))


def _wall_clock_differences(source: str) -> list[int]:
    """Lines of ``source`` on which a subtraction involves ``time.time()``
    or ``time.perf_counter()``, directly or through a name bound from one."""
    import ast

    def is_wall_call(node):
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("time", "perf_counter")
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "time")

    tree = ast.parse(source)
    tainted = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and is_wall_call(node.value):
            tainted.update(t.id for t in node.targets
                           if isinstance(t, ast.Name))
    return sorted(
        node.lineno for node in ast.walk(tree)
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub)
        and any(is_wall_call(s) or (isinstance(s, ast.Name) and s.id in tainted)
                for s in (node.left, node.right)))


@pytest.mark.parametrize("source, lines", [
    ("import time\nt0 = time.time()\nwork()\ndt = time.time() - t0\n", [4]),
    ("import time\nstart = time.perf_counter()\nwork()\n"
     "end = time.perf_counter()\nprint(end - start)\n", [5]),
    ("import time\nt0 = time.monotonic()\nwork()\n"
     "dt = time.monotonic() - t0\nstamp = time.time()\n", []),
], ids=["direct_time", "names_from_perf_counter", "monotonic_and_bare_stamp"])
def test_wall_clock_scanner_sees_what_it_forbids(source, lines):
    """Positive control of the rule below: a scanner that finds nothing
    would pass it on any tree."""
    assert _wall_clock_differences(source) == lines


def test_no_wall_clock_differencing_around_device_work():
    """No subtraction in ``tdfo_tpu/`` may involve ``time.time()`` /
    ``time.perf_counter()`` (or a name bound from one): such a difference
    that does not end in a sync measures dispatch, not compute.  Speed is
    measured on the benchmark's clock — ``benchmarks/lib/monitor.py::now``
    around whole ``Trainer.train_epoch`` calls that end in a value fetch,
    device times from the profiler trace (``PERF.md`` section 2) — and
    host-loop time goes through ``obs.trace.clock()``.  Bare timestamp USE
    (no differencing) is untouched."""
    from pathlib import Path

    import tdfo_tpu

    offenders = [
        f"{path}:{ln}"
        for path in sorted(Path(tdfo_tpu.__file__).parent.rglob("*.py"))
        for ln in _wall_clock_differences(path.read_text())]
    assert not offenders, (
        "time.time()/time.perf_counter() differencing in tdfo_tpu/ — speed "
        "is measured by the benchmark (benchmarks/lib/monitor.py::now around "
        "whole Trainer.train_epoch calls that end in a value fetch, PERF.md "
        "section 2); use obs.trace.clock() for host-loop wall time: "
        + ", ".join(offenders))


LIVING_DOCUMENTS = ("README.md", "CLAUDE.md", "docs/PARITY.md",
                    "docs/BUDGET.md", ".claude/skills/verify/SKILL.md")


@pytest.mark.parametrize("document", LIVING_DOCUMENTS)
def test_living_documents_name_files_that_exist(document):
    """Every backticked path under the checkout's own directories, and every
    backticked root-level ``*.py``, resolves.  ``:line`` / ``::name``
    suffixes and trailing punctuation are dropped; a path with a
    placeholder (``<name>``, ``*``, ``{a,b}``) names no one file and is
    skipped.  Out of the pattern on purpose: run-time artefacts
    (``metrics.jsonl``) and the reference's files, which the documents
    write with their backend directory (``jax-flax/train_dp.py``)."""
    import re
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    owned = re.compile(
        r"^(?:(?:tdfo_tpu|tests|benchmarks|configs|docs|tools)/[\w./-]*|\w+\.py)$")
    # fenced blocks are not backticked spans; a span may wrap over a line
    # end but not over a paragraph, so one stray backtick cannot flip the
    # pairing of everything after it
    text = re.sub(r"```.*?```", "", (repo / document).read_text(), flags=re.S)
    named = set()
    for span in re.findall(r"`((?:[^`\n]|\n(?!\s*\n))+)`", text):
        for token in span.split():
            token = token.strip("()[],;\"'").split(":", 1)[0].rstrip(".")
            if owned.match(token):
                named.add(token)
    assert named  # the pattern sees this document's paths
    missing = sorted(t for t in named if not (repo / t).exists())
    assert not missing, f"{document} names files that do not exist: {missing}"


def test_monotonic_differencing_and_id_minting_confined_to_trace_module():
    """``obs/trace.py`` is the single sanctioned home for host-loop
    interval timing (``clock()``/``elapsed_ms()``/``elapsed_s()``) and for
    span-id minting (a locked deterministic counter).  Two sub-rules:

      * no ``time.monotonic()`` CALL, and no subtraction involving one (or
        a name bound from one, or from ``trace.clock()``), outside
        obs/trace.py — every wall-time measurement flows through one
        auditable site.  Injectable-clock ATTRIBUTE calls
        (``self._clock()``, the watchdog/frontend deadline machinery) and
        bare ``time.monotonic`` references passed as defaults stay legal:
        they are the test seam, not a timing fork.
      * no ``uuid``/``secrets`` import anywhere in the package — random
        ids would break restart determinism, and the causal join keys are
        domain ids (replica, seq, cycle, version), so nothing ever needs
        one.

    Self-tested on synthetic offenders."""
    import ast
    from pathlib import Path

    import tdfo_tpu

    root = Path(tdfo_tpu.__file__).parent
    files = sorted(root.rglob("*.py"))
    SANCTIONED = "obs/trace.py"

    def is_mono_call(node):
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "monotonic"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "time")

    def is_trace_clock_call(node):
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "clock"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in ("trace", "_trace", "obs_trace"))

    def scan(tree):
        """-> (mono_call_lines, sub_lines, mint_lines)"""
        mono, subs, mints = [], [], []
        parents = {}
        for node in ast.walk(tree):
            for ch in ast.iter_child_nodes(node):
                parents[ch] = node

        def enclosing_fn(node):
            while node in parents:
                node = parents[node]
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    return node
            return None

        # taint is FUNCTION-scoped: an unrelated `t0` in another function
        # (e.g. an injectable-clock deadline) must not inherit it
        tainted = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and (
                    is_mono_call(node.value) or is_trace_clock_call(node.value)):
                fn = enclosing_fn(node)
                tainted.update((t.id, fn) for t in node.targets
                               if isinstance(t, ast.Name))
        for node in ast.walk(tree):
            if is_mono_call(node):
                mono.append(node.lineno)
            if (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub)
                    and any(is_mono_call(s) or is_trace_clock_call(s)
                            or (isinstance(s, ast.Name)
                                and (s.id, enclosing_fn(node)) in tainted)
                            for s in (node.left, node.right))):
                subs.append(node.lineno)
            if isinstance(node, ast.Import):
                mints += [node.lineno for a in node.names
                          if a.name.split(".")[0] in ("uuid", "secrets")]
            if (isinstance(node, ast.ImportFrom) and node.module
                    and node.module.split(".")[0] in ("uuid", "secrets")):
                mints.append(node.lineno)
        return sorted(set(mono)), sorted(set(subs)), sorted(set(mints))

    synthetic = (
        "import time, uuid\n"
        "from tdfo_tpu.obs import trace\n"
        "def span(trace_id=None):\n"
        "    t0 = time.monotonic()\n"
        "    work()\n"
        "    dur = time.monotonic() - t0\n"
        "    tid = trace_id or str(uuid.uuid4())\n"
        "    t1 = trace.clock()\n"
        "    return dur, tid, trace.clock() - t1\n")
    m, s, i = scan(ast.parse(synthetic))
    assert m == [4, 6] and s == [6, 9] and i == [1]

    offenders, sanctioned_hits = [], 0
    for path in files:
        rel = str(path.relative_to(root))
        mono, subs, mints = scan(ast.parse(path.read_text(),
                                           filename=str(path)))
        if rel == SANCTIONED:
            assert not mints  # the sanctioned timer never mints random ids
            sanctioned_hits += len(mono) + len(subs)
            continue
        offenders += [f"{path}:{ln} (monotonic call/differencing)"
                      for ln in sorted(set(mono) | set(subs))]
        offenders += [f"{path}:{ln} (uuid/secrets import)" for ln in mints]
    assert sanctioned_hits > 0  # the scanner sees the sanctioned site
    assert not offenders, (
        "monotonic-clock timing or random id minting outside obs/trace.py "
        "— route intervals through trace.clock()/elapsed_ms() and use "
        "domain ids (replica, seq, cycle, version) as join keys: "
        + ", ".join(offenders))


def test_no_cost_constants_outside_cost_model():
    """`tdfo_tpu/plan/costs.py` is the single sanctioned home for measured
    per-descriptor cost constants (the executable docs/BUDGET.md): a
    `*_NS`/`*_US`/`*_MS` number hardcoded anywhere else is a fork of the
    chip measurements that the planner's calibration test cannot see, and
    the two copies WILL drift.  The rule: no module-level ALL_CAPS
    assignment whose name carries an NS/US/MS unit segment outside
    plan/costs.py.  Matching is on `_`-split SEGMENTS, so names like
    CONTINUOUS_COLS stay legal."""
    import ast
    from pathlib import Path

    import tdfo_tpu

    root = Path(tdfo_tpu.__file__).parent
    files = sorted(root.rglob("*.py"))
    sanctioned = root / "plan" / "costs.py"

    def is_cost_name(name: str) -> bool:
        if not name.isupper():
            return False
        return bool({"NS", "US", "MS"} & set(name.split("_")))

    offenders, sanctioned_hits = [], 0
    for path in files:
        tree = ast.parse(path.read_text(), filename=str(path))
        # module level only: locals named like units are not constant forks
        for node in tree.body:
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets = [node.target]
            for t in targets:
                if isinstance(t, ast.Name) and is_cost_name(t.id):
                    if path == sanctioned:
                        sanctioned_hits += 1
                    else:
                        offenders.append(f"{path}:{node.lineno} {t.id}")
    assert sanctioned_hits > 0  # the scanner sees the sanctioned module
    assert not offenders, (
        "measured cost constants outside tdfo_tpu/plan/costs.py (the single "
        "home for chip numbers — add it there with provenance and import "
        "it): " + ", ".join(offenders))


def test_no_precisionless_dots_in_kernel_code():
    """f32 `dot_general` INSIDE Mosaic kernels silently runs bf16 passes at
    default precision (~1e-3 rel error — enough to poison optimizer state;
    CLAUDE.md measured fact).  Every dot in ops/pallas_kernels.py must state
    its precision explicitly: HIGHEST where exactness matters, an explicit
    DEFAULT where bf16 MXU passes are the intent (the flash-attention dots).
    Implicit precision is how the bug comes back."""
    import ast
    from pathlib import Path

    import tdfo_tpu

    path = Path(tdfo_tpu.__file__).parent / "ops" / "pallas_kernels.py"
    tree = ast.parse(path.read_text(), filename=str(path))
    offenders = []
    n_dots = 0
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("dot_general", "dot")):
            continue
        n_dots += 1
        if "precision" not in {k.arg for k in node.keywords}:
            offenders.append(f"{path.name}:{node.lineno}")
    assert n_dots > 0  # the rule must actually be scanning something
    assert not offenders, (
        "dot_general/dot without explicit precision= in kernel code "
        "(default precision runs bf16 passes on f32 operands): "
        + ", ".join(offenders))


def test_no_bare_renames_outside_atomic_swap_helpers():
    """Crash safety is only as strong as its narrowest rename: a bare
    ``os.rename``/``os.replace`` (or keywordless ``.rename()`` method call —
    the ``Path.rename`` shape) outside the blessed helpers skips the
    fsync-file + replace + fsync-dir discipline, and a crash at that site
    leaves a torn pointer or a half-published bundle
    (``tdfo_tpu/serve/swap.py`` docstring).  The ONLY sanctioned sites are
    ``atomic_write_json`` and ``publish_dir`` there, plus
    ``utils/logrotate.py``'s ``rotate_path`` (which renames a CLOSED,
    complete diagnostics file — nothing half-written to protect).
    Keyworded ``.rename`` calls (pandas column renames) are host-side and
    exempt."""
    import ast
    from pathlib import Path

    import tdfo_tpu

    root = Path(tdfo_tpu.__file__).parent
    SANCTIONED = {("serve/swap.py", "atomic_write_json"),
                  ("serve/swap.py", "publish_dir"),
                  ("utils/logrotate.py", "rotate_path")}

    offenders, sanctioned_hits = [], 0
    for path in sorted(root.rglob("*.py")):
        rel = str(path.relative_to(root))
        tree = ast.parse(path.read_text(), filename=str(path))
        parents = {}
        for node in ast.walk(tree):
            for ch in ast.iter_child_nodes(node):
                parents[ch] = node

        def enclosing_funcs(node):
            out = []
            while node in parents:
                node = parents[node]
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    out.append(node.name)
            return out

        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)):
                continue
            f = node.func
            is_os_rename = (f.attr in ("rename", "replace")
                            and isinstance(f.value, ast.Name)
                            and f.value.id == "os")
            is_method_rename = (f.attr == "rename"
                                and not is_os_rename
                                and not node.keywords)
            if not (is_os_rename or is_method_rename):
                continue
            if any((rel, fn) in SANCTIONED for fn in enclosing_funcs(node)):
                sanctioned_hits += 1
                continue
            offenders.append(f"{path}:{node.lineno}")
    assert sanctioned_hits >= 3  # the scanner sees every blessed helper
    assert not offenders, (
        "bare rename outside serve/swap.py's atomic helpers (not crash-"
        "safe — route through atomic_write_json/publish_dir, or "
        "logrotate.rotate_path for closed diagnostics files): "
        + ", ".join(offenders))


def test_no_hand_rolled_retry_sleep_loops():
    """``utils/retry.py`` is the single backoff law (bounded attempts,
    jittered exponential delay, JSONL failure records, fault-injection
    hook).  A hand-rolled ``while/for + try + time.sleep`` retry loop
    anywhere else dodges all four — silent unbounded retries are how a
    wedged job burns a TPU reservation.  The detector flags any
    ``time.sleep`` call lexically inside a loop that also contains a
    ``try`` (the retry-loop shape); one-shot sleeps (the ``[faults]``
    stall/slow injections) stay legal.  The detector is self-tested on a
    synthetic offender because the package rightly contains none."""
    import ast
    from pathlib import Path

    import tdfo_tpu

    root = Path(tdfo_tpu.__file__).parent

    def retry_sleep_lines(tree):
        hits = []
        for node in ast.walk(tree):
            if not isinstance(node, (ast.For, ast.While)):
                continue
            body = list(ast.walk(node))
            has_try = any(isinstance(n, ast.Try) for n in body)
            for n in body:
                if (isinstance(n, ast.Call)
                        and isinstance(n.func, ast.Attribute)
                        and n.func.attr == "sleep"
                        and isinstance(n.func.value, ast.Name)
                        and n.func.value.id == "time"
                        and has_try):
                    hits.append(n.lineno)
        return hits

    synthetic = (
        "import time\n"
        "def naive(fn):\n"
        "    while True:\n"
        "        try:\n"
        "            return fn()\n"
        "        except OSError:\n"
        "            time.sleep(1.0)\n")
    assert retry_sleep_lines(ast.parse(synthetic)) == [7]

    offenders = []
    for path in sorted(root.rglob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        offenders += [f"{path}:{ln}" for ln in retry_sleep_lines(tree)]
    assert not offenders, (
        "hand-rolled time.sleep retry loop (use utils/retry.py retry_call: "
        "bounded attempts, jittered backoff, JSONL records, fault hook): "
        + ", ".join(offenders))


def test_no_int8_casts_outside_quant_module():
    """``ops/quant.py`` owns the int8 grid: codes are only meaningful next
    to their per-row f32 (scale, offset) sidecar, and only
    ``quantize_rows``/``dequantize_rows`` know the grid (scale =
    (rmax-rmin)/255, offset = rmin + 128*scale, SR keyed by (step,
    table_id)).  An ``.astype(jnp.int8)`` / ``.view(jnp.int8)`` /
    ``bitcast_convert_type(..., jnp.int8)`` anywhere else mints codes with
    no sidecar (silent garbage on dequant) or re-grids stored codes
    outside the stamp the checkpoints refuse on — both unrecoverable
    after the fact.  Casts FROM int8 (``codes.astype(jnp.bfloat16)`` in
    the coarse scan) stay legal, as does host-side ``np.int8`` (labels,
    parquet).  Self-tested on a synthetic offender."""
    import ast
    from pathlib import Path

    import tdfo_tpu

    root = Path(tdfo_tpu.__file__).parent
    sanctioned = root / "ops" / "quant.py"

    def names_int8(node):
        # jnp.int8 / jax.numpy.int8, or the "int8" dtype string
        if isinstance(node, ast.Constant):
            return node.value == "int8"
        if not (isinstance(node, ast.Attribute) and node.attr == "int8"):
            return False
        base = node.value
        return (isinstance(base, ast.Name) and base.id == "jnp") or (
            isinstance(base, ast.Attribute) and base.attr == "numpy"
            and isinstance(base.value, ast.Name) and base.value.id == "jax")

    def int8_cast_lines(tree):
        hits = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("astype", "view",
                                           "bitcast_convert_type")):
                continue
            operands = list(node.args) + [k.value for k in node.keywords]
            if any(names_int8(a) for a in operands):
                hits.append(node.lineno)
        return hits

    synthetic = (
        "import jax.numpy as jnp\n"
        "def sneak(x):\n"
        "    return x.astype(jnp.int8)\n")
    assert int8_cast_lines(ast.parse(synthetic)) == [3]

    offenders, sanctioned_hits = [], 0
    for path in sorted(root.rglob("*.py")):
        lines = int8_cast_lines(ast.parse(path.read_text(),
                                          filename=str(path)))
        if path == sanctioned:
            sanctioned_hits += len(lines)
            continue
        offenders += [f"{path}:{ln}" for ln in lines]
    assert sanctioned_hits > 0  # the scanner sees quantize_rows' cast
    assert not offenders, (
        "cast to int8 outside ops/quant.py (codes without their (scale, "
        "offset) sidecar are garbage — route through quantize_rows/"
        "dequantize_rows): " + ", ".join(offenders))


def test_no_adhoc_jsonl_tailers():
    """``data/replay.py`` is the single sanctioned reader of line-oriented
    JSONL streams: it owns torn-tail truncation, seal digest verification,
    seq dedup and the byte-offset cursor that make replay exactly-once.  A
    hand-rolled ``for line in ...: json.loads(line)`` tailer anywhere else
    silently skips ALL of that — it would happily train on a torn or
    corrupted log.  The detector flags any ``json.loads`` call lexically
    inside a ``for``/``while`` loop in the package, outside the blessed
    readers: ``data/replay.py`` itself, ``plan/stats.py`` (which streams
    its OWN stats artifact, written atomically as a complete file — not a
    live log) and ``obs/aggregate.py`` (which assembles its OWN trace
    sinks — complete-line appends with no cursor to bypass; it skips, never
    parses, a live writer's torn tail).  Whole-file
    ``json.loads(path.read_text())`` reads are loop-free and stay legal.
    Self-tested on a synthetic offender."""
    import ast
    from pathlib import Path

    import tdfo_tpu

    root = Path(tdfo_tpu.__file__).parent
    BLESSED = {"data/replay.py", "plan/stats.py", "obs/aggregate.py"}

    def loop_loads_lines(tree):
        hits = []
        for node in ast.walk(tree):
            if not isinstance(node, (ast.For, ast.While)):
                continue
            for n in ast.walk(node):
                if (isinstance(n, ast.Call)
                        and isinstance(n.func, ast.Attribute)
                        and n.func.attr == "loads"
                        and isinstance(n.func.value, ast.Name)
                        and n.func.value.id == "json"):
                    hits.append(n.lineno)
        return sorted(set(hits))

    synthetic = (
        "import json\n"
        "def tail(path):\n"
        "    out = []\n"
        "    for line in open(path):\n"
        "        out.append(json.loads(line))\n"
        "    return out\n")
    assert loop_loads_lines(ast.parse(synthetic)) == [5]

    offenders, blessed_hits = [], 0
    for path in sorted(root.rglob("*.py")):
        rel = str(path.relative_to(root))
        lines = loop_loads_lines(ast.parse(path.read_text(),
                                           filename=str(path)))
        if rel in BLESSED:
            blessed_hits += len(lines)
            continue
        offenders += [f"{path}:{ln}" for ln in lines]
    assert blessed_hits > 0  # the scanner sees the sanctioned reader
    assert not offenders, (
        "ad-hoc JSONL line tailer (json.loads inside a loop) outside "
        "data/replay.py — it bypasses torn-tail recovery, seal digests and "
        "the exactly-once cursor; read through ReplayConsumer: "
        + ", ".join(offenders))


def test_no_pointer_writes_outside_swap_store_helpers():
    """The ``CURRENT``/``CANARY`` pointers are the serving fleet's single
    source of truth: every replica follows them, the canary state machine's
    crash windows are proven ONLY for the write orderings inside
    ``serve/swap.py`` (pointer-first canary publish, CURRENT-first
    promotion — see its docstring).  An ``atomic_write_json`` whose
    argument names either pointer anywhere else is an unvetted state
    machine transition: it can regress CURRENT past a verdict or publish
    an unvetted canary.  Sanctioned writers: ``_publish``, ``recover``,
    ``publish_canary``, ``promote_canary``, ``rollback_canary`` in
    serve/swap.py.  Self-tested on a synthetic offender."""
    import ast
    from pathlib import Path

    import tdfo_tpu

    root = Path(tdfo_tpu.__file__).parent
    SANCTIONED_FILE = "serve/swap.py"
    SANCTIONED_FUNCS = {"_publish", "recover", "publish_canary",
                        "promote_canary", "rollback_canary"}

    def names_pointer(node):
        # the module constants _CURRENT/_CANARY, or their literal values
        for n in ast.walk(node):
            if isinstance(n, ast.Name) and n.id in ("_CURRENT", "_CANARY"):
                return True
            if isinstance(n, ast.Constant) and n.value in ("CURRENT",
                                                           "CANARY"):
                return True
        return False

    def pointer_write_lines(tree):
        parents = {}
        for node in ast.walk(tree):
            for ch in ast.iter_child_nodes(node):
                parents[ch] = node

        def enclosing_funcs(node):
            out = []
            while node in parents:
                node = parents[node]
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    out.append(node.name)
            return out

        hits = []
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            is_writer = (isinstance(f, ast.Name)
                         and f.id == "atomic_write_json") or (
                isinstance(f, ast.Attribute)
                and f.attr == "atomic_write_json")
            if not is_writer:
                continue
            operands = list(node.args) + [k.value for k in node.keywords]
            if any(names_pointer(a) for a in operands):
                hits.append((node.lineno, enclosing_funcs(node)))
        return hits

    synthetic = (
        "from tdfo_tpu.serve.swap import atomic_write_json\n"
        "def hijack(store, v):\n"
        "    atomic_write_json(store.root / 'CURRENT', {'version': v})\n")
    syn = pointer_write_lines(ast.parse(synthetic))
    assert [(ln, fns) for ln, fns in syn] == [(3, ["hijack"])]

    offenders, sanctioned_hits = [], 0
    for path in sorted(root.rglob("*.py")):
        rel = str(path.relative_to(root))
        for ln, fns in pointer_write_lines(
                ast.parse(path.read_text(), filename=str(path))):
            if rel == SANCTIONED_FILE and SANCTIONED_FUNCS & set(fns):
                sanctioned_hits += 1
                continue
            offenders.append(f"{path}:{ln}")
    assert sanctioned_hits >= 3  # _publish + publish_canary + promote/recover
    assert not offenders, (
        "CURRENT/CANARY pointer write outside serve/swap.py's blessed "
        "helpers (unvetted canary state machine transition — route through "
        "publish_canary/promote_canary/rollback_canary): "
        + ", ".join(offenders))


def test_no_hard_exits_outside_fault_injector():
    """``os._exit`` skips every durability mechanism this repo builds on —
    atexit hooks, finally blocks, buffered writes.  That is exactly what
    the fault injector WANTS (a real preemption gives no notice, so the
    kill triggers in ``utils/faults.py`` must model it faithfully) and
    exactly what production code must never do: a convenience hard-exit in
    a serving or training path would turn an error into silent data loss
    that the kill/restart tests cannot see.  Self-tested on a synthetic
    offender."""
    import ast
    from pathlib import Path

    import tdfo_tpu

    root = Path(tdfo_tpu.__file__).parent

    def hard_exit_lines(tree):
        hits = []
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "_exit"
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "os"):
                hits.append(node.lineno)
        return hits

    synthetic = (
        "import os\n"
        "def bail():\n"
        "    os._exit(1)\n")
    assert hard_exit_lines(ast.parse(synthetic)) == [3]

    offenders, sanctioned_hits = [], 0
    for path in sorted(root.rglob("*.py")):
        rel = str(path.relative_to(root))
        lines = hard_exit_lines(ast.parse(path.read_text(),
                                          filename=str(path)))
        if rel == "utils/faults.py":
            sanctioned_hits += len(lines)
            continue
        offenders += [f"{path}:{ln}" for ln in lines]
    assert sanctioned_hits > 0  # the scanner sees the kill triggers
    assert not offenders, (
        "os._exit outside utils/faults.py (skips atexit/finally/buffers — "
        "raise, or route deterministic kills through the fault injector): "
        + ", ".join(offenders))


def test_sockets_and_process_spawning_confined_to_serve_plumbing():
    """``serve/wire.py`` owns the socket monopoly (length-prefixed framing,
    max-frame refusal, connect-retry through the single backoff law) and
    ``serve/supervisor.py`` owns process spawning (respawn backoff, flap
    quarantine, child reaping).  A raw ``socket.socket`` or
    ``subprocess.Popen`` anywhere else in the package dodges framing,
    frame-size limits, retry budgets and child supervision — exactly the
    failure modes the kill -9 drills exist to catch.  ``subprocess.run``
    (bounded, reaped — ``native/__init__.py``) and pure lookups like
    ``socket.gethostname`` stay legal.  Self-tested on a synthetic
    offender."""
    import ast
    from pathlib import Path

    import tdfo_tpu

    root = Path(tdfo_tpu.__file__).parent
    BLESSED = {"serve/wire.py", "serve/supervisor.py"}
    SOCKET_CTORS = {"socket", "create_connection", "create_server",
                    "socketpair"}

    def spawn_lines(tree):
        hits = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)):
                continue
            mod, attr = node.func.value.id, node.func.attr
            if (mod == "socket" and attr in SOCKET_CTORS) or (
                    mod == "subprocess" and attr == "Popen"):
                hits.append(node.lineno)
        return hits

    synthetic = (
        "import socket, subprocess\n"
        "def sneak(path):\n"
        "    s = socket.socket(socket.AF_UNIX)\n"
        "    host = socket.gethostname()\n"        # legal: pure lookup
        "    subprocess.run(['true'])\n"           # legal: bounded + reaped
        "    return subprocess.Popen(['sleep', '9'])\n")
    assert spawn_lines(ast.parse(synthetic)) == [3, 6]

    offenders, sanctioned_hits = [], 0
    for path in sorted(root.rglob("*.py")):
        rel = str(path.relative_to(root))
        lines = spawn_lines(ast.parse(path.read_text(), filename=str(path)))
        if rel in BLESSED:
            sanctioned_hits += len(lines)
            continue
        offenders += [f"{path}:{ln}" for ln in lines]
    assert sanctioned_hits >= 2  # wire's listener/dial + supervisor's Popen
    assert not offenders, (
        "raw socket/process spawning outside serve/wire.py + "
        "serve/supervisor.py (dodges framing, frame limits, retry budgets "
        "and child supervision — route through wire.listen/wire.connect or "
        "ProcessSupervisor): " + ", ".join(offenders))


def test_pad_mask_id_literals_confined_to_protocol_homes():
    """``models/bert4rec.py`` and ``data/seq_preprocessing.py`` are the two
    homes of the sequence id protocol (``PAD_ID = 0``, ``MASK = n_items +
    1``, items 1-based — torchrec/preprocessing.py:14-15).  A literal
    re-declaration anywhere else (``PAD_ID = 0`` in a serving module) is a
    fork: if the protocol ever moves, the fork silently pads with a REAL
    item id and every downstream ranking is garbage with no error.  The
    rule: no int-literal assignment to a PAD/MASK-named constant outside
    the two homes — serving code must IMPORT ``PAD_ID`` (derivations like
    ``mask_id = n_items + 1`` from an imported ``n_items`` stay legal, and
    the importer audit below proves the serve path actually does import).
    Self-tested on a synthetic offender."""
    import ast
    from pathlib import Path

    import tdfo_tpu

    root = Path(tdfo_tpu.__file__).parent
    HOMES = {"models/bert4rec.py", "data/seq_preprocessing.py"}

    def fork_lines(tree):
        hits = []
        for node in ast.walk(tree):
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets = [node.target]
            else:
                continue
            # int literals only: bools and None are not id constants, and
            # derivations (BinOp over an imported n_items) are not forks
            if not (isinstance(node.value, ast.Constant)
                    and type(node.value.value) is int):
                continue
            for t in targets:
                if isinstance(t, ast.Name) and (
                        {"PAD", "MASK"} & set(t.id.upper().split("_"))):
                    hits.append(node.lineno)
        return hits

    def pad_id_import_srcs(tree):
        return [node.module for node in ast.walk(tree)
                if isinstance(node, ast.ImportFrom) and node.module
                and any(a.name == "PAD_ID" for a in node.names)]

    synthetic = (
        "PAD_ID = 0\n"
        "MASK_TOKEN = 122\n"
        "from tdfo_tpu.models.bert4rec import PAD_ID\n"
        "def window(n_items):\n"
        "    mask_id = n_items + 1\n"   # legal: a derivation, not a fork
        "    return mask_id\n")
    tree = ast.parse(synthetic)
    assert fork_lines(tree) == [1, 2]
    assert pad_id_import_srcs(tree) == ["tdfo_tpu.models.bert4rec"]

    offenders, home_hits, importers = [], 0, {}
    for path in sorted(root.rglob("*.py")):
        rel = str(path.relative_to(root))
        tree = ast.parse(path.read_text(), filename=str(path))
        lines = fork_lines(tree)
        srcs = pad_id_import_srcs(tree)
        if srcs:
            importers[rel] = srcs
        if rel in HOMES:
            home_hits += len(lines)
            continue
        offenders += [f"{path}:{ln}" for ln in lines]
    assert home_hits >= 2  # the scanner sees both protocol homes
    assert not offenders, (
        "PAD/MASK id literal outside models/bert4rec.py + "
        "data/seq_preprocessing.py (a fork of the sequence id protocol — "
        "import PAD_ID instead): " + ", ".join(offenders))
    # every importer pulls PAD_ID from a protocol home (no third-party
    # re-export to drift behind), and the serve path IS an importer — the
    # rule has teeth where it matters
    home_mods = {"tdfo_tpu." + h[:-3].replace("/", ".") for h in HOMES}
    for rel, srcs in importers.items():
        assert set(srcs) <= home_mods, (rel, srcs)
    assert "serve/seq_scoring.py" in importers
