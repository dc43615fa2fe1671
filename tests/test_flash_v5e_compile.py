"""The main path's kernels — flash attention and the sparse layers' grouped
matmul — compile for a described v5e (Mosaic + the XLA TPU compiler, no chip
attached) at the benchmark cells' shapes.

Interpret mode cannot see what the chip's compiler refuses — a slice off
the (8, 128) tiling, more VMEM than a kernel may take — and these compiles
can (/opt/skills/guides/on-chip-measurement §2). Nothing runs, so this says
nothing about results or times. One file, one fixture: only the worker that
is handed this file loads libtpu.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.ops.grouped_matmul import grouped_matmul


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # libtpu logs to /tmp/tpu_logs unless told not to: nothing of a test
    # run may land outside the checkout, HOME and TMPDIR
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip writes cache entries nobody can read back (each
    # later compile would warn): cache off around these tests
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


# (B, S, q heads, kv heads, D, window): what the benchmark's cells trace,
# bf16, block 512
CELLS = {
    "cgpt13b": (8, 2048, 16, 16, 128, None),
    "sc2-3b_s4k": (4, 4096, 24, 2, 128, 4096),
    "sc2-3b_s16k": (1, 16384, 24, 2, 128, 4096),
    # laguna-s21_s8k: groups of 6 and 9 query heads on the one kv head
    # held, no window on the full layers, window 512 = one block
    "laguna_full": (2, 8192, 6, 1, 128, None),
    "laguna_window": (2, 8192, 9, 1, 128, 512),
}


def _shapes(cell, sharding):
    b, s, h, h_kv, d, window = CELLS[cell]
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, s, h_kv, d), jnp.bfloat16,
                              sharding=sharding)
    return q, kv, window


@pytest.mark.parametrize("cell", CELLS)
def test_flash_forward_compiles_for_v5e(one_chip, cell):
    q, kv, window = _shapes(cell, one_chip)
    text = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, True, 512, False, window)).lower(
            q, kv, kv).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("cell", CELLS)
def test_flash_vjp_compiles_for_v5e(one_chip, cell):
    """Forward + dQ + dK/dV: the three Mosaic calls of one layer's
    attention in the train step."""
    q, kv, window = _shapes(cell, one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, 512, False, window)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


# (rows, contraction, columns): laguna-s21_s8k's expert matrices over one
# chunk of sorted rows (moe.chunk_rows at 16,384 tokens), 8 experts, bf16
@pytest.mark.parametrize("shape", [(10240, 3072, 1024),
                                   (10240, 1024, 3072)])
def test_grouped_matmul_vjp_compiles_for_v5e(one_chip, shape):
    """gmm forward, gmm for the rows' gradient, tgmm for the matrices'."""
    m, k, n = shape
    rows = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    mats = jax.ShapeDtypeStruct((8, k, n), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)

    def loss(a, b, g):
        out = grouped_matmul(a, b, g, jnp.bfloat16)
        return (out.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        rows, mats, sizes).compile().as_text()
    calls = [l for l in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in l]
    assert len(calls) == 3 and all("hvd_gmm" in l for l in calls)
