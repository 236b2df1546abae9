"""Test bootstrap: 8 spoofed CPU devices BEFORE jax initialises.

This is the framework-wide realisation of the reference's fake-cluster hints
(SURVEY.md §4.1: jax-flax/train_dp.py:21-24 commented XLA_FLAGS, TF logical
devices, in-process gRPC PS cluster, torchrec mp.spawn) — every multi-device
test in the suite runs on an 8-device virtual CPU mesh.
"""

import collections

from tdfo_tpu.core.mesh import spoof_cpu_devices

spoof_cpu_devices(8)

import jax  # noqa: E402

jax.config.update("jax_default_matmul_precision", "highest")
# hermetic suite: launch.main / chip_smoke place a persistent compile cache
# in the checkout (core/mesh.configure_compile_cache); tests must neither
# fill it nor pass because of a stale entry in it
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh

    return make_mesh(MeshSpec(data=4, model=2, seq=1))


@pytest.fixture(scope="session")
def mesh_dp():
    from tdfo_tpu.core.config import MeshSpec
    from tdfo_tpu.core.mesh import make_mesh

    return make_mesh(MeshSpec(data=8, model=1, seq=1))


# Under ``-n 6 --dist loadfile`` a file is one unit of work on one worker, and
# xdist queues the files by their count of tests.  ``test_update_cache.py``
# holds few tests but the longest: its eager trajectories hold one worker for
# 950-1,200 s, mostly compiling single operations.  Queued 21st, it started
# after the other workers had taken most of the suite and ended the run some
# 250 s after they were idle.  So the files are queued longest first: the
# files named here, then the rest by their count of tests, as xdist would.
LONG_FILES = ("test_update_cache.py",)


def pytest_configure(config):
    # xdist queues the files in the order collected (below), not by count
    config.option.loadscopereorder = False


@pytest.hookimpl(trylast=True)
def pytest_collection_modifyitems(config, items):
    count = collections.Counter(item.path.name for item in items)
    items.sort(key=lambda item: (item.path.name not in LONG_FILES,
                                 -count[item.path.name]))
