"""Grouped matrix multiplication for the sparse FFNs (models/moe.py
``moe_dropless``): ``out[i] = lhs[i] @ rhs[group of row i]`` for rows
sorted by group, ``group_sizes[g]`` consecutive rows per group.

The kernels are jax's own Pallas TPU grouped matmuls (``jax.experimental.
pallas.ops.tpu.megablox``: ``gmm`` forward and for the gradient of the
rows, ``tgmm`` for the gradient of the matrices), called under the device
scope ``hvd_gmm`` so that a trace files their time under the program's
names — ``lax.ragged_dot`` computes the same, but the TPU compiler turns it
into calls that carry no scope at all (``ragged-dot-none``), and on a v5e
its backward took twice as long at these shapes (PERF.md section 6, PR 27).

Only as many row tiles run as hold a row of some group, so rows past the
last group cost nothing — and come back UNDEFINED (unwritten memory): the
caller masks them.
"""

import jax
from jax.experimental.pallas.ops.tpu.megablox import ops as _megablox

#: rows x contraction x columns of one tile: the fastest of five tried on
#: a v5e at 10,240 x 3,072 x 1,024 in bf16 (PERF.md section 6, PR 27); a
#: 1,024-row tile does not fit the kernel's VMEM
TILE = (256, 1024, 1024)


def grouped_matmul(lhs, rhs, group_sizes, out_dtype, interpret=False):
    """lhs: (m, k) rows sorted by group, m a multiple of 256 or below it;
    rhs: (groups, k, n); group_sizes: (groups,) int32, sum <= m. Returns
    (m, n) in ``out_dtype`` (accumulated in float32); differentiable in
    ``lhs`` and ``rhs``."""
    tiling = tuple(min(tile, size) for tile, size in zip(
        TILE, (lhs.shape[0], lhs.shape[1], rhs.shape[2])))
    with jax.named_scope("hvd_gmm"):
        return _megablox.gmm(lhs, rhs, group_sizes, out_dtype, tiling,
                             None, None, False, interpret)
