"""The main path's kernels, compiled for a described TPU v5e at real widths.

Interpret-mode tests (``test_pallas_kernels.py``, ``test_sparse_ops.py``)
check the kernels' math everywhere but cannot see what only the chip's
compiler refuses: DMA slices off the lane tiling, more semaphores than the
~2 KB sflag space, blocks that do not tile (8, 128), vmem.  The installed TPU
compiler does see them, for a chip that is described and not attached
(``/opt/skills/guides/on-chip-measurement`` section 2, rehearsal 3) — so each
case here lowers the real entry point with ``ShapeDtypeStruct`` arguments
placed on a described ``v5e:2x2`` device and compiles it.  Nothing runs: a
compile that passes is not a chip run (``python chip_smoke.py`` is).

Rules this file keeps (they are why it is ONE file with plain fixtures): the
topology is described inside a module-scoped, non-autouse fixture that skips
when it cannot be — never at import, in a ``skipif``, in ``parametrize``
arguments or in ``conftest.py`` — because only one process may load the TPU
library and every xdist worker imports every test file; everything compiles
in the test's own process; the persistent compilation cache is off
(``conftest.py``), since such a compile could be written to it but never read
back without a chip.
"""

import re

import jax
import jax.numpy as jnp

import pytest
from jax.sharding import SingleDeviceSharding

from tdfo_tpu.core.mesh import PALLAS_CHOICES
from tdfo_tpu.ops import sparse
from tdfo_tpu.ops.pallas_kernels import (
    fat_gather_rows,
    flash_attention,
    line_layout,
)

V, U = 500_000, 8192  # table rows, ids per step (chip_smoke's user table, BATCH)


@pytest.fixture(scope="module")
def topo():
    import os

    # or the compiler logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _fat_args(one_chip, d, kind, dtype):
    """(layout, abstract fat table of V rows, abstract-array maker)."""
    lay = line_layout(d, kind, dtype)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    return lay, s((lay.n_lines(V), lay.tiles, 128), dtype), s


@pytest.mark.parametrize("d,kind,dtype,sr,rows_per_line", [
    (64, "adam", jnp.float32, False, 1),
    (64, "adam", jnp.bfloat16, True, 1),    # _sr_writeback in the kernel
    (16, "rowwise_adagrad", jnp.float32, False, 4),
])
def test_fat_line_update_compiles(one_chip, d, kind, dtype, sr,
                                  rows_per_line):
    """``_fat_apply_lines`` -> ``fat_line_update``: per-line DMAs on the 3D
    fat layout, (2, 128) DMA semaphores, in-place aliasing.  The operands
    are what ``fat_update``'s dedupe hands over (its sort is plain XLA and
    not what this file is about)."""
    lay, fat, s = _fat_args(one_chip, d, kind, dtype)
    assert lay.r == rows_per_line
    slots = (s((), jnp.int32),) if kind == "adam" else ()
    ulines, key = s((U,), jnp.int32), s((2,), jnp.uint32)
    g_slots = s((U * lay.r, d), jnp.float32)
    touched = None if lay.r == 1 else s((U * lay.r,), jnp.float32)

    def step(fat, slots, ulines, g_slots, touched, key):
        return sparse._fat_apply_lines(
            fat, slots, ulines, g_slots, touched, layout=lay, lr=1e-2,
            weight_decay=0.01, platform="tpu",
            sr_key=jax.random.wrap_key_data(key) if sr else None)

    before = PALLAS_CHOICES[("fat_line_update", "kernel", "tpu")]
    text = _compile(step, fat, slots, ulines, g_slots, touched, key)
    assert "tpu_custom_call" in text
    # the kernel's name= reaches the compiled program (a trace's tf_op)
    assert "fat_line_update/pallas_call" in text
    assert PALLAS_CHOICES[("fat_line_update", "kernel", "tpu")] == before + 1


@pytest.mark.parametrize("d,kind,rows_per_line", [
    (16, "rowwise_adagrad", 4), (16, "adam", 2)])
def test_fat_line_update_routed_compiles(one_chip, d, kind, rows_per_line):
    """``fat_apply_routed`` -> ``fat_line_update_routed`` (the dedup-lookup
    flow): the kernel routes row-level grads into packed lanes itself and
    reuses the forward's line gather; r > 1 at d = 16.  Operands as
    ``dedupe_rows_and_lines`` + the forward hand them over."""
    lay, fat, s = _fat_args(one_chip, d, kind, jnp.float32)
    assert lay.r == rows_per_line > 1
    slots = (s((), jnp.int32),) if kind == "adam" else ()
    idx = s((U,), jnp.int32)
    lines = s((U, lay.tiles, 128), jnp.float32)

    def step(fat, slots, ulines, g_u, row_lidx, row_slot, lines):
        return sparse.fat_apply_routed(
            fat, slots, ulines, g_u, row_lidx, row_slot, lines,
            embedding_dim=d, kind=kind, lr=1e-2, weight_decay=0.01,
            platform="tpu")

    before = PALLAS_CHOICES[("fat_line_update_routed", "kernel", "tpu")]
    text = _compile(step, fat, slots, idx, s((U, d), jnp.float32), idx, idx,
                    lines)
    assert "tpu_custom_call" in text
    assert "fat_line_update_routed/pallas_call" in text
    assert PALLAS_CHOICES[
        ("fat_line_update_routed", "kernel", "tpu")] == before + 1


@pytest.mark.parametrize("d,kind", [(64, "adam"), (16, "rowwise_adagrad")])
def test_fat_gather_rows_compiles(one_chip, d, kind):
    """The lookup off fat lines: full-line gather on dim 0 of the 3D array,
    slot-select on the gathered block (no re-tiling of the table)."""
    lay, fat, s = _fat_args(one_chip, d, kind, jnp.float32)
    text = _compile(lambda fat, ids: fat_gather_rows(fat, ids, lay), fat,
                    s((U,), jnp.int32))
    assert "gather" in text


@pytest.mark.parametrize("shape,dtype", [
    ((4, 2, 8192, 64), jnp.bfloat16),   # long context, 512-blocks
    ((256, 2, 20, 32), jnp.bfloat16),   # configs/bert4rec.toml's own width
    ((256, 2, 20, 32), jnp.float32),    # ... without mixed_precision
    ((8, 2, 200, 64), jnp.bfloat16),    # T not a multiple of the lane tile
])
def test_flash_attention_fwd_bwd_compiles(one_chip, shape, dtype):
    """``attn = "flash"``: forward with the lse residual and the two
    FlashAttention-2 backward kernels.  Short / ragged T pads up to whole
    128-lane blocks (``_clip_blocks``) — 16-wide blocks were refused by
    Mosaic at Bert4Rec's T = 20."""
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    valid = jax.ShapeDtypeStruct(shape[:1] + shape[2:3], jnp.bool_,
                                 sharding=one_chip)

    def loss(q, k, v, valid):
        return flash_attention(q, k, v, valid).astype(jnp.float32).sum()

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, x, x, valid)
    assert text.count("tpu_custom_call") >= 3  # fwd, dq, dk/dv
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        # op_name="jit(loss)/transpose(jvp(flash_bwd_dq))/pallas_call"
        assert re.search(rf'op_name="[^"]*\b{kernel}\b[^"]*/pallas_call"',
                         text), kernel
