import jax
import numpy as np
import pytest

from tdfo_tpu.core.config import MeshSpec
from tdfo_tpu.core.mesh import make_mesh


def test_eight_devices_spoofed():
    assert jax.device_count() == 8


def test_wildcard_axis():
    mesh = make_mesh(MeshSpec(data=-1, model=2))
    assert mesh.shape == {"data": 4, "model": 2, "seq": 1}


def test_full_dp():
    mesh = make_mesh(MeshSpec(data=-1))
    assert mesh.shape["data"] == 8


def test_bad_sizes():
    with pytest.raises(ValueError):
        make_mesh(MeshSpec(data=3, model=2))
    with pytest.raises(ValueError):
        make_mesh(MeshSpec(data=-1, model=-1))


def test_sharded_array_placement(mesh8):
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = jax.device_put(np.arange(16.0).reshape(8, 2), NamedSharding(mesh8, P("data", None)))
    assert len(x.addressable_shards) == 8
    assert x.addressable_shards[0].data.shape == (2, 2)


# ------------------------------------------------- compile cache placement


_CACHE_PROBE = """
import os, sys
from tdfo_tpu.core.mesh import configure_compile_cache
import jax
got = configure_compile_cache()
again = configure_compile_cache()
assert got == again
print(got)
print(jax.config.jax_compilation_cache_dir)
"""


def _probe_cache(env_dir):
    import os
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                         cwd=repo, capture_output=True, text=True, check=True)
    returned, configured = out.stdout.strip().splitlines()[-2:]
    return returned, configured


def test_compile_cache_env_var_wins_and_code_sets_nothing(tmp_path,
                                                          monkeypatch):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, jax reads it itself and the
    helper leaves the config alone (in this process: exactly as found)."""
    from tdfo_tpu.core.mesh import configure_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    # a fresh process: jax itself picked the variable up
    assert _probe_cache(str(tmp_path)) == (str(tmp_path), str(tmp_path))


def test_compile_cache_defaults_to_one_fixed_path_in_the_checkout():
    """Unset: ``<checkout>/.jax_cache`` — the same string from two calls and
    two processes (a temp name, pid or time in it would never hit), and a
    path ``.gitignore`` lists."""
    from pathlib import Path

    repo = Path(__file__).resolve().parents[1]
    want = str(repo / ".jax_cache")
    assert _probe_cache(None) == (want, want)
    assert _probe_cache(None) == (want, want)
    assert ".jax_cache/" in (repo / ".gitignore").read_text().split()


# ------------------------------------------------- distributed bootstrap


@pytest.fixture
def dist_calls(monkeypatch):
    """Records ``jax.distributed.initialize`` calls instead of making them;
    every multi-process variable starts unset."""
    calls = []
    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: calls.append(kw))
    for var in ("WORLD_SIZE", "RANK", "COORDINATOR_ADDRESS",
                "TPU_WORKER_HOSTNAMES", "MEGASCALE_NUM_SLICES"):
        monkeypatch.delenv(var, raising=False)
    return calls


def test_single_process_never_initialises_distributed(dist_calls, capsys):
    from tdfo_tpu.launch import _init_distributed

    _init_distributed("auto")
    _init_distributed("never")
    assert dist_calls == []
    assert "single-process run" in capsys.readouterr().out
    with pytest.raises(SystemExit, match="no multi-process environment"):
        _init_distributed("always")
    assert dist_calls == []


@pytest.mark.parametrize("env,want", [
    ({"WORLD_SIZE": "2", "RANK": "1", "COORDINATOR_ADDRESS": "localhost:1"},
     {"coordinator_address": "localhost:1", "num_processes": 2,
      "process_id": 1}),
    ({"TPU_WORKER_HOSTNAMES": "host-0,host-1"}, {}),
    ({"MEGASCALE_NUM_SLICES": "2"}, {}),
])
def test_described_multiprocess_env_initialises(dist_calls, monkeypatch, env,
                                                want):
    from tdfo_tpu.launch import _init_distributed

    for k, v in env.items():
        monkeypatch.setenv(k, v)
    _init_distributed("auto")
    assert dist_calls == [want]


def test_failed_distributed_init_is_fatal(dist_calls, monkeypatch):
    from tdfo_tpu.launch import _init_distributed

    def boom(**kw):
        raise RuntimeError("coordinator unreachable")

    monkeypatch.setattr(jax.distributed, "initialize", boom)
    monkeypatch.setenv("WORLD_SIZE", "2")
    with pytest.raises(RuntimeError, match="coordinator unreachable"):
        _init_distributed("auto")


# --------------------------------------------- implementation by platform


def test_pallas_impl_is_decided_by_the_devices_platform():
    from tdfo_tpu.core.mesh import PALLAS_CHOICES, mesh_platform, pallas_impl

    assert mesh_platform() == mesh_platform(make_mesh()) == "cpu"
    assert pallas_impl("op", "tpu", off_chip="xla") == "kernel"
    assert pallas_impl("op", "tpu", off_chip="interpret") == "kernel"
    assert pallas_impl("op", "cpu", off_chip="xla") == "xla"
    assert pallas_impl("op", "cpu", off_chip="interpret") == "interpret"
    assert pallas_impl("op", "cuda", off_chip="xla") == "xla"
    with pytest.raises(ValueError, match="interpret mode is for CPU"):
        pallas_impl("op", "cuda", off_chip="interpret")
    with pytest.raises(ValueError, match="no implementation for devices"):
        pallas_impl("op", "not-a-platform", off_chip="xla")
    assert PALLAS_CHOICES[("op", "kernel", "tpu")] == 2


def test_fat_line_dispatch_refuses_instead_of_falling_back():
    """On TPU devices the fused update is the kernel or an error — a width
    the kernels do not cover must not quietly train on the formulation that
    re-tiles the whole table every step."""
    from tdfo_tpu.ops.pallas_kernels import line_layout
    from tdfo_tpu.ops.sparse import _fat_impl

    wide = line_layout(256, "adam")
    assert _fat_impl("fat_line_update", wide, "cpu", True) == "xla"
    with pytest.raises(NotImplementedError, match="fused_table_threshold"):
        _fat_impl("fat_line_update", wide, "tpu", False)
    assert _fat_impl("fat_line_update", line_layout(64, "adam"), "tpu",
                     False) == "kernel"


@pytest.mark.parametrize("platform,want", [
    ("tpu", "bfloat16"), ("gpu", "float16"), ("cuda", "float16"),
    ("rocm", "float16"), ("cpu", "float32")])
def test_compute_dtype_by_platform(platform, want):
    from tdfo_tpu.core.precision import compute_dtype

    assert np.dtype(compute_dtype(True, platform)).name == want
    assert np.dtype(compute_dtype(False, platform)).name == "float32"


def test_compute_dtype_refuses_unknown_platform():
    from tdfo_tpu.core.precision import compute_dtype

    with pytest.raises(ValueError, match="unknown device platform"):
        compute_dtype(True, "not-a-platform")
