"""The compiled step's gradient exchange psums the gradient LEAVES: no
flat wire row is concatenated before the all-reduce or sliced after it.

Two pins, both readable without a chip:

- the optimized HLO of a small ``TransformerLM`` step has no instruction of
  the flat ``[sum of leaf sizes]`` shape and no ``concatenate`` under
  ``hvd_exchange``, on a world of one and on four devices; on four, the
  all-reduce operands are the gradient, byte for byte;
- the per-leaf exchange against the arithmetic it replaced — ``concatenate
  -> psum -> unfuse_segments``, which the eager engine's wire programs
  still run (ops/collectives.py) — bit for bit.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import step_program
from horovod_tpu.ops.collectives import (exchange_bucket_plan,
                                         segment_health, unfuse_segments)
from horovod_tpu.ops.compression import Compression

# ----------------------------------------------------------------- the HLO

CFG = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=64, max_seq=16, dtype=jnp.float32)
_SHAPE = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]")
_ALL_REDUCE = re.compile(r"= (\(.*?\)|\S+) all-reduce(?:-start)?\(")


def _step_hlo(world, buckets):
    """Optimized HLO of the compiled psum step over ``world`` devices,
    and the gradient's element count."""
    mesh = Mesh(np.array(jax.devices()[:world]), ("hvd",))
    axes = tfm.ShardAxes(dp=None, sp=None, tp=None)

    def loss_fn(p, tokens, targets):
        return tfm.loss_fn(p, tokens, targets, CFG, axes)

    tx = optax.adamw(3e-4)
    params = jax.eval_shape(lambda k: tfm.init_params(k, CFG),
                            jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2 * world, 16), jnp.int32)
    prog = step_program._build_step_program(
        mesh, loss_fn, tx, 2, "psum", True, None, False, False, False,
        None, buckets)
    hlo = prog.lower(params, jax.eval_shape(tx.init, params), tok,
                     tok).compile().as_text()
    return hlo, sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))


@pytest.mark.parametrize("world,buckets", [(1, 1), (4, 1), (4, 3)])
def test_step_hlo_has_no_flat_row(world, buckets):
    hlo, n = _step_hlo(world, buckets)
    flat = re.compile(rf"\bf32\[{n}\]")
    for line in hlo.splitlines():
        assert not flat.search(line), f"flat gradient row: {line.strip()}"
        if "hvd_exchange" in line:
            assert " concatenate(" not in line, line.strip()
    wire = 0
    for m in _ALL_REDUCE.finditer(hlo):
        for dt, dims in _SHAPE.findall(m.group(1)):
            if dims:  # the loss's pmean is a scalar
                wire += (int(np.prod([int(d) for d in dims.split(",")]))
                         * int(re.sub(r"\D", "", dt)) // 8)
    if world > 1:  # (a world of one's all-reduce is for the backend to drop)
        assert wire == 4 * n


# ------------------------------------------------------------ the numerics

def _row_exchange(leaves, axes, average, comp, n, buckets):
    """The replaced arithmetic: per bucket and wire dtype, flatten the
    leaves into one row, psum the row, ``unfuse_segments`` it back;
    health rows by ``segment_health`` on the reduced row."""
    out, hrows = [None] * len(leaves), [None] * len(leaves)
    for idxs in exchange_bucket_plan(leaves, buckets):
        wire = {i: (leaves[i] if comp is None
                    else comp.compress(leaves[i])[0]) for i in idxs}
        for name in sorted({w.dtype.name for w in wire.values()}):
            group = [i for i in idxs if wire[i].dtype.name == name]
            segs, off = [], 0
            for i in group:
                segs.append((off, leaves[i].size, tuple(leaves[i].shape),
                             leaves[i].dtype.name, average, None))
                off += leaves[i].size
            row = lax.psum(jnp.concatenate(
                [wire[i].reshape(-1) for i in group]), axes)
            res, hr = unfuse_segments(row, segs, n), segment_health(row, segs)
            for k, i in enumerate(group):
                out[i], hrows[i] = res[k], hr[k]
    return out, jnp.stack(hrows)


@pytest.mark.parametrize("dtype", ["bfloat16", "float16", "float32",
                                   "int32"])
def test_unfuse_segments_average_rounds_once(dtype):
    """A float segment's average is the mean rounded once to the
    segment's type — bfloat16 too, which numpy's ``issubdtype`` does not
    count as floating and the row used to floor-divide; an integer
    segment floor-divides."""
    rng = np.random.RandomState(3)
    a, b = (jnp.asarray(rng.randn(12) * 7, dtype) for _ in range(2))
    (got,) = unfuse_segments(a + b, ((0, 12, (3, 4), dtype, True, None),), 2)
    if dtype == "int32":
        want = (a + b) // 2
    else:
        want = ((a.astype(jnp.float32) + b.astype(jnp.float32)) / 2
                ).astype(dtype)
    assert got.dtype == want.dtype and got.shape == (3, 4)
    np.testing.assert_array_equal(np.asarray(got).ravel(), np.asarray(want))


def _leaves(kind, ranks):
    """Per-rank gradient leaves ``(ranks, ...)``, a different value on
    every rank."""
    rng = np.random.RandomState(7)
    f32 = [rng.randn(ranks, 8, 6), rng.randn(ranks, 5),
           rng.randn(ranks, 3, 4, 2), rng.randn(ranks, 16)]
    if kind == "f32":
        return [jnp.asarray(a, jnp.float32) for a in f32]
    if kind == "bf16":
        return [jnp.asarray(a, jnp.bfloat16) for a in f32]
    return [jnp.asarray(f32[0], jnp.float32),
            jnp.asarray(rng.randint(-50, 50, (ranks, 7)), jnp.int32),
            jnp.asarray(f32[2], jnp.bfloat16),
            jnp.asarray(f32[3], jnp.float32)]


@pytest.mark.parametrize("buckets", [1, 3])
@pytest.mark.parametrize("average", [True, False])
@pytest.mark.parametrize("layout", ["flat", "expert_group"])
@pytest.mark.parametrize("kind,comp", [
    ("f32", None), ("bf16", None), ("mixed_int", None),
    ("f32", Compression.fp16), ("mixed_int", Compression.bf16)])
def test_leaf_exchange_matches_row_exchange(kind, comp, layout, average,
                                            buckets):
    """Bit for bit the wire row's results, and health rows that every
    rank reads alike."""
    devs = np.array(jax.devices()[:8])
    if layout == "flat":
        mesh, axes, denom, n = Mesh(devs, ("hvd",)), "hvd", None, 8
    else:
        # the 2-D MoE mesh's expert leaves: summed over the data axis,
        # divided by the whole world
        mesh = Mesh(devs.reshape(4, 2), ("hvd", "ep"))
        axes, denom, n = ("hvd",), 8, 8
    leaves = _leaves(kind, 8)

    def per_shard(*ls):
        ls = [a[0] for a in ls]
        got, hg = step_program._psum_exchange(
            ls, axes, average, comp, True, denom=denom, buckets=buckets)
        want, hw = _row_exchange(ls, axes, average, comp, n, buckets)
        return tuple(a[None] for a in (*got, hg, *want, hw))

    spec = P(mesh.axis_names)
    outs = jax.jit(jax.shard_map(
        per_shard, mesh=mesh, in_specs=spec, out_specs=spec,
        check_vma=False))(*leaves)
    k = len(leaves)
    got, hg, want, hw = outs[:k], outs[k], outs[k + 1:2 * k + 1], outs[-1]
    for g, w, leaf in zip(got, want, leaves):
        assert g.dtype == w.dtype == leaf.dtype and g.shape == leaf.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    hg = np.asarray(hg)
    assert hg.shape == (8, k, 2)
    # every rank of a reduce group holds the same reduced leaves
    group = hg.reshape(4, 2, k, 2) if layout == "expert_group" else hg[:, None]
    for col in range(group.shape[1]):
        for r in range(1, group.shape[0]):
            np.testing.assert_array_equal(group[r, col], group[0, col])
    np.testing.assert_allclose(hg, np.asarray(hw), rtol=1e-6)
