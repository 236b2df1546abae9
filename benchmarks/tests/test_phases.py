"""The readers of the program's epoch phase records, on hand-made histories:
the median over the window's epochs, the warm-up (epoch 0) and an earlier
run's epochs left out, an error where the window is empty or a phase every
epoch runs is missing, and nothing from a program that keeps no records."""

import pytest

from benchmarks import run
from benchmarks.lib import phases

CTX = {"batch": 100, "rate": 100 * 30 / 64.0}  # 30 window steps in 64 s


def record(epoch, steps, loop_s, **named):
    return {"epoch": epoch, "steps": steps, "loop_s": loop_s,
            "phases": {k: list(v) for k, v in named.items()}}


def epoch(e, scale, **over):
    """An epoch of 10 steps whose every phase is ``scale`` times a base."""
    base = dict(epoch_open=(1.0, 1, 1.0), next_batch=(4.0, 9, 1.5),
                loader_next=(3.0, 10, 1.0), h2d_put=(0.5, 10, 0.1),
                dispatch=(2.0, 10, 0.4), loss_sync=(1.0, 2, 0.75),
                epoch_close=(1.0, 1, 1.0))
    named = {k: (s * scale, n, m * scale) for k, (s, n, m) in base.items()}
    named.update(over)
    return record(e, 10, 20.0 * scale, **{k: v for k, v in named.items()
                                          if v is not None})


HISTORY = [
    epoch(0, 7.0), epoch(1, 9.0),                   # an earlier run of this process
    epoch(0, 5.0),                                  # this run's warm-up
    epoch(1, 1.0), epoch(2, 2.0), epoch(3, 0.2),    # its window: median = epoch 1
]

EXPECTED = {
    "input_wait_pct": 20.0, "loader_next_ms": 300.0, "h2d_put_ms": 50.0,
    "next_batch_max_ms": 1500.0, "dispatch_ms": 200.0, "loss_sync_ms": 100.0,
    "epoch_boundary_pct": 10.0,
    "window_accounted_pct": 100.0 * (20.0 + 40.0 + 4.0) / 64.0,
}


@pytest.fixture
def history(monkeypatch):
    from tdfo_tpu.obs import trace

    def put(records):
        monkeypatch.setattr(trace, "epoch_history", lambda: list(records))

    return put


def readers(bench):
    cell = bench["workloads"][0]
    return {name: read for name, _, read in run.metric_readers(bench, cell)
            if name in EXPECTED}


def test_benchmark_json_names_the_eight_readers(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert set(EXPECTED) <= set(by_name)
    for name in EXPECTED:
        m = by_name[name]
        assert m["source"] == "program_span" and "workloads" not in m
        assert m["moves"] == "train_examples_per_s"
        assert m["layer"] in ("input", "host loop")


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_takes_the_median_over_the_windows_epochs(bench, history, name):
    history(HISTORY)
    assert readers(bench)[name](CTX) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_an_empty_window_is_an_error_never_a_zero(bench, history, name):
    for records in ([], HISTORY[:3]):
        history(records)
        with pytest.raises(RuntimeError, match="none of an epoch >= 1"):
            readers(bench)[name](CTX)


def test_a_phase_every_epoch_runs_is_an_error_when_missing(bench, history):
    history([epoch(0, 1.0), epoch(1, 1.0, h2d_put=None, epoch_open=None)])
    read = readers(bench)
    for name in ("h2d_put_ms", "epoch_boundary_pct"):
        with pytest.raises(RuntimeError, match="has no phase"):
            read[name](CTX)
    assert read["dispatch_ms"](CTX) == pytest.approx(200.0)


def test_a_program_without_the_records_reads_as_nothing(bench, monkeypatch):
    from tdfo_tpu.obs import trace

    monkeypatch.delattr(trace, "epoch_history")  # the parent commit
    for name, read in readers(bench).items():
        assert read(CTX) is None, name


def test_a_truncated_history_still_reads(bench, history):
    """More than the history's 64 epochs in one window: the warm-up has been
    pushed out, every record left is the window's."""
    history(HISTORY[3:])
    assert readers(bench)["dispatch_ms"](CTX) == pytest.approx(200.0)
