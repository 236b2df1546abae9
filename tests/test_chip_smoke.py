"""``chip_smoke.py`` rehearsed on CPU devices at a tiny size.

The script's phase functions take their sizes as arguments; these tests
import the module and drive the SAME functions in-process (rehearsals 1 and 2
of the on-chip-measurement guide: control flow on CPU, the sharded path on
virtual devices).  ``main()`` always runs the full width and always demands
the chip, so here it must refuse.  Nothing in this file proves anything about
the chip — that is what ``python chip_smoke.py`` on the chip is for.
"""

import json
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402

# 26 tables like the real profile: two big enough to dominate the stack, the
# rest small (incl. vocab-3/4 tables like Criteo's)
TINY_VOCABS = tuple([4000, 2500] + [3 + (i * 37) % 90 for i in range(24)])
BATCH, STEPS = 32, 6


def test_constants_are_the_published_profiles():
    from test_planner import CRITEO_VOCABS  # pytest puts tests/ on sys.path

    vocabs = chip_smoke.CRITEO_KAGGLE_VOCABS
    assert len(vocabs) == 26 and sum(vocabs) == 33_762_577
    assert max(vocabs) == 10_131_227
    assert vocabs == CRITEO_VOCABS  # the planner's calibration profile
    assert chip_smoke.TWOTOWER_SIZE_MAP == {
        "user": 500_000, "item": 200_000, "language": 32, "is_ebook": 2,
        "format": 16, "publisher": 5_000, "pub_decade": 16,
    }


def test_data_generator_writes_the_preprocess_criteo_format(tmp_path):
    """Same files, columns, arrow types and size_map keys as the real ETL
    (``launch synth-criteo`` + ``launch preprocess-criteo``)."""
    import pyarrow.parquet as pq

    from tdfo_tpu.data.criteo_preprocessing import run_criteo_preprocessing
    from tdfo_tpu.data.synthetic import write_synthetic_criteo

    etl, ours = tmp_path / "etl", tmp_path / "ours"
    write_synthetic_criteo(etl, n_rows=600)
    etl_map = run_criteo_preprocessing(etl)
    our_map = chip_smoke.write_criteo_data(
        ours, TINY_VOCABS, n_train=500, n_eval=100, seed=3)

    assert list(our_map) == list(etl_map)
    assert json.loads((ours / "size_map.json").read_text()) == our_map
    for split in ("train", "eval"):
        a = sorted((etl / "parquet").glob(f"{split}_part_*.parquet"))
        b = sorted((ours / "parquet").glob(f"{split}_part_*.parquet"))
        assert a and b
        assert pq.read_schema(a[0]).remove_metadata() == \
            pq.read_schema(b[0]).remove_metadata()
    rows = pq.read_table(ours / "parquet").to_pydict()
    assert len(rows["label"]) == 600 and set(rows["label"]) == {0, 1}
    for c, v in our_map.items():
        assert 0 <= min(rows[c]) and max(rows[c]) < v
    assert 0.0 <= min(rows["cont_0"]) and max(rows["cont_12"]) <= 1.0


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """data -> ``launch train`` at a tiny size, shared by the round-trip
    tests (one compile of the train and eval programs)."""
    work = tmp_path_factory.mktemp("smoke")
    n_dev = len(jax.devices())
    chip_smoke.write_criteo_data(
        work / "data", TINY_VOCABS, n_train=STEPS * BATCH * n_dev,
        n_eval=2 * BATCH * n_dev, seed=0)
    cfg = chip_smoke.write_config(
        work / "smoke.toml", data_dir=work / "data",
        checkpoint_dir=work / "ckpt", batch=BATCH, use_tpu=False,
        log_every_n_steps=2, learning_rate=3e-3)
    out = chip_smoke.phase_train(cfg, steps_per_epoch=STEPS, n_epochs=2)
    return cfg, out


def test_config_is_the_committed_one_but_for_the_run(trained):
    import tomllib

    cfg, _ = trained
    ours = tomllib.loads(Path(cfg).read_text())
    committed = tomllib.loads(chip_smoke.DLRM_CRITEO_TOML.read_text())
    changed = {k for k in ours if ours[k] != committed.get(k)}
    assert changed == {"data_dir", "checkpoint_dir", "use_tpu",
                       "per_device_train_batch_size",
                       "per_device_eval_batch_size", "log_every_n_steps",
                       "learning_rate"}
    assert set(committed) <= set(ours)


def test_launch_train_then_serve_restores_the_checkpoint(trained):
    """``launch train`` -> ``launch serve`` round-trips; serve restores
    step > 0 (not its fresh-init path) and the bundle serves what the
    trainer's eval step computes."""
    cfg, out = trained
    assert out["trained_steps"] == 2 * STEPS
    assert out["eval_losses"][-1] < out["eval_losses"][0]
    served = chip_smoke.phase_serve(cfg, trained_steps=out["trained_steps"])
    assert served["step"] == 2 * STEPS > 0
    got = chip_smoke.phase_verify(cfg, served["bundle_dir"], n_rows=50,
                                  platform="cpu", atol=1e-5)
    assert got["max_abs_diff"] <= 1e-5


def test_serve_without_the_checkpoint_is_caught(trained, tmp_path):
    """The fresh-init path of ``serve_from_config`` must not pass for a
    restore: with the checkpoint gone, phase_serve fails its step check."""
    import shutil

    from tdfo_tpu.core.config import read_configs

    cfg, out = trained
    src = read_configs(cfg)
    moved = chip_smoke.write_config(
        tmp_path / "nockpt.toml", data_dir=src.data_dir,
        checkpoint_dir=tmp_path / "empty", batch=BATCH, use_tpu=False)
    with pytest.raises(AssertionError, match="was not what was served"):
        chip_smoke.phase_serve(moved, trained_steps=out["trained_steps"])
    shutil.rmtree(tmp_path / "empty", ignore_errors=True)


def test_kernels_phase_names_the_implementation_that_ran():
    """On CPU devices the fat-line steps take the XLA formulation and say
    so; the kernel-vs-XLA comparison runs the interpreted kernel."""
    from tdfo_tpu.core.mesh import PALLAS_CHOICES

    sizes = dict(chip_smoke.TWOTOWER_SIZE_MAP, user=40_000, item=20_000,
                 publisher=50)
    out = chip_smoke.phase_kernels(
        sizes, embed_dim=16, batch=8, steps=2, seed=0, platform="cpu",
        flash_shape=(2, 2, 20, 16))
    assert out["fat_max_abs_diff"] <= chip_smoke.KERNEL_ATOL * 10
    assert PALLAS_CHOICES[("fat_line_update", "xla", "cpu")] > 0
    assert PALLAS_CHOICES[("fat_line_update_routed", "xla", "cpu")] > 0
    assert PALLAS_CHOICES[("fat_line_update", "interpret", "cpu")] > 0


def test_multichip_phase_on_four_virtual_devices(tmp_path):
    """Rehearsal 2: the ``--multichip`` phase over four virtual CPU devices
    — table rows land on four distinct devices, the step has all-to-all,
    losses match the one-device run."""
    out = chip_smoke.phase_multichip(
        tmp_path, TINY_VOCABS, batch=BATCH, steps=3, seed=0,
        devices=jax.devices()[:4], platform="cpu")
    assert out["all_to_all"] > 0
    assert out["max_rel_loss_diff"] <= chip_smoke.MULTICHIP_RTOL


def test_main_refuses_to_run_without_the_chip(monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    for argv in ([], ["--multichip"]):
        with pytest.raises(SystemExit) as e:
            chip_smoke.main(argv)
        assert e.value.code not in (0, None)
    # no option waives the device check
    with pytest.raises(SystemExit):
        chip_smoke.main(["--allow-cpu"])
    assert '"ok"' not in capsys.readouterr().out
