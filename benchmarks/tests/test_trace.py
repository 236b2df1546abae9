"""The trace reduction on a small synthetic trace: union of intervals, idle
share, per-pattern time, top operations, gap attribution."""

import pytest

from benchmarks.lib import trace as T
from benchmarks.lib.trace import Event, Plane


def synthetic():
    # device: two executions of one program; ops overlap and nest
    ops = [
        Event("%fusion.1 = f32[100,16]{1,0} fusion(...)", 1000, 400),
        Event("%scatter.2 = f32[100,16]{1,0} scatter(...)", 1200, 400),   # overlaps: union 1000..1600
        Event("%copy.3 = f32[100,16]{1,0} copy(...)", 1300, 100),          # nested
        Event("%fusion.1 = f32[100,16]{1,0} fusion(...)", 3000, 500),
        Event("%custom.4 = f32[8,2,128]{2,1,0} custom-call(...)", 3500, 500,
              meta="custom_call_target=tpu_custom_call"),
    ]
    modules = [Event("jit__step(1)", 1000, 600), Event("jit__step(1)", 3000, 1000),
               Event("jit_small(2)", 2000, 10)]
    dev = Plane("/device:TPU:0", {T.OPS_LINE: ops, T.MODULES_LINE: modules})
    empty = Plane("/device:TPU:1", {T.OPS_LINE: []})
    host = Plane("/host:CPU", {"main": [Event("bench:next_batch", 1500, 1400),
                                        Event("other", 1650, 100)]})
    return [dev, empty, host]


def test_union_counts_overlap_once():
    ops = synthetic()[0].lines[T.OPS_LINE]
    assert T.union_ns(ops) == 600 + 1000
    assert T.span_ns(ops) == (1000, 4000)
    assert T.union_ns([]) == 0


def test_summary_idle_share_steps_and_gaps():
    s = T.summarise(synthetic())
    assert s.busy_s == pytest.approx(1600e-9)
    assert s.window_s == pytest.approx(3000e-9)
    assert s.idle_share == pytest.approx(1 - 1600 / 3000)
    assert (s.module, s.steps) == ("jit__step(1)", 2)
    assert s.idle == [(1600, 3000)]


def test_no_device_plane_gives_nothing():
    assert T.summarise([synthetic()[2]]) is None
    assert T.summarise(synthetic(), platform="cpu") is None


def test_pattern_time_and_top_ops():
    ops = synthetic()[0].lines[T.OPS_LINE]
    assert T.union_ns(T.matching(ops, r"^%(fusion|scatter)\.\d+ = f32\[100,16\]")) == 600 + 500
    assert [e.name[:9] for e in T.matching(ops, "tpu_custom_call")] == ["%custom.4"]
    assert T.matching(ops, "all-to-all") == []
    top = T.top_ops(ops, 2)
    assert top[0][0].startswith("%fusion.1") and top[0][1] == pytest.approx(900e-9)
    assert len(top) == 2 and all(len(n) <= T.NAME_CHARS for n, _ in top)


def test_gaps_go_to_the_host_event_that_overlaps_longest():
    planes = synthetic()
    s = T.summarise(planes)
    assert T.attribute_gaps(s.idle, [planes[2]]) == [["bench:next_batch", pytest.approx(1400e-9)]]
    assert T.attribute_gaps(s.idle, [])[0][0] == "unattributed"


def test_load_reads_what_the_profiler_writes(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:unit"):
        jnp.ones((8, 8)).sum().block_until_ready()
    jax.profiler.stop_trace()
    planes = T.load(tmp_path)
    names = {e.name for p in planes for evs in p.lines.values() for e in evs}
    assert "bench:unit" in names
    with pytest.raises(FileNotFoundError):
        T.load(tmp_path / "nothing")
