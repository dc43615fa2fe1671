"""What the compiled step asks of the compiler where it exchanges across
chips (ops/step_program.py ``_exchange_compiler_options``), and the
counter that reads what the compiler made of it
(diag/xla_trace.py ``exchange_async``).

Readable without a chip: the options are derived from the mesh's devices
and from nothing else — none on one device, on the CPU backend or where
the optimizer exchanges by itself, so those programs are the bare jit's —
and the counter is a parse of optimized-HLO text. The compile for a
described v5e:2x2 that shows the options doing something is in
tests/test_flash_v5e_compile.py, the one file that loads libtpu.
"""

import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.diag import xla_trace
from horovod_tpu.ops import step_program
from test_exchange_leaves import _row_exchange

# ------------------------------------------------------------- the options


class _Chip:
    platform = "tpu"


def _tpu_mesh(n):
    """As much of a mesh as the derivation looks at."""
    return types.SimpleNamespace(
        devices=np.array([_Chip() for _ in range(n)], dtype=object))


def _cpu_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("hvd",))


@pytest.mark.parametrize("mesh,exchange", [
    (_tpu_mesh(1), "psum"), (_tpu_mesh(4), "none"), (_tpu_mesh(1), "none"),
    ("cpu1", "psum"), ("cpu4", "psum"), ("cpu8", "psum"), ("cpu8", "none")])
def test_no_options_without_an_exchange_across_tpu_chips(mesh, exchange):
    if isinstance(mesh, str):
        mesh = _cpu_mesh(int(mesh[3:]))
    assert step_program._exchange_compiler_options(mesh, exchange) == {}


@pytest.mark.parametrize("shape", [(4,), (2, 2), (1, 4)])
@pytest.mark.parametrize("threshold_known", [True, False])
def test_options_across_tpu_chips(monkeypatch, shape, threshold_known):
    """Both public options, and the combiner's threshold where the
    compiler knows the name; without it the two alone, not a failure."""
    asked = []
    monkeypatch.setattr(
        step_program, "_compiler_accepts",
        lambda device, option: asked.append(option) or threshold_known)
    mesh = _tpu_mesh(4)
    mesh.devices = mesh.devices.reshape(shape)
    got = step_program._exchange_compiler_options(mesh, "psum")
    want = {"xla_enable_async_all_reduce": True,
            "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": True}
    if threshold_known:
        want["xla_jf_crs_combiner_threshold_in_bytes"] = 32 << 20
    assert got == want
    assert asked == [step_program._COMBINER_THRESHOLD]


# ----------------------------------------------- the CPU program's values

def _loss(p, x, y):
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    return jnp.mean((h @ p["w2"] - y) ** 2)


@pytest.mark.parametrize("world", [1, 2, 4, 8])
def test_cpu_step_runs_with_the_row_exchanges_values(world):
    """``_build_step_program`` on the CPU passes no options (the backend
    would refuse them), lowers, runs, and lands bit for bit where the
    flat wire row's arithmetic lands."""
    mesh = _cpu_mesh(world)
    rng = np.random.RandomState(5)
    params = {"w1": jnp.asarray(rng.randn(6, 13) * 0.3, jnp.float32),
              "b1": jnp.zeros((13,), jnp.float32),
              "w2": jnp.asarray(rng.randn(13, 3) * 0.3, jnp.float32)}
    x = jnp.asarray(rng.randn(16, 6), jnp.float32)
    y = jnp.asarray(rng.randn(16, 3), jnp.float32)
    tx = optax.adamw(1e-2)
    state = tx.init(params)
    prog = step_program._build_step_program(
        mesh, _loss, tx, 2, "psum", True, None, False, False, False)
    got = prog(params, state, x, y)

    def per_shard(p, s, a, b):
        loss, bwd = jax.vjp(lambda q: _loss(q, a, b), p)
        (grads,) = bwd(jnp.ones_like(loss))
        leaves, treedef = jax.tree.flatten(grads)
        out, _ = _row_exchange(leaves, ("hvd",), True, None, world, 1)
        updates, s = tx.update(jax.tree.unflatten(treedef, out), s, p)
        return optax.apply_updates(p, updates), s, lax.pmean(loss, "hvd")

    want = jax.jit(jax.shard_map(
        per_shard, mesh=mesh, in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=P(), check_vma=False))(params, state, x, y)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------------------------------------------- the counter

# cgpt13b_dp4's step compiled for a described v5e:2x2, cut down to one
# fused all-reduce (with the start / done fusions that repeat it), one
# variadic all-reduce left in ENTRY, and the loss's scalar
_TAIL = (', channel_id=1, replica_groups={{0,1,2,3}}, '
         'use_global_device_ids=true, to_apply=%region_112.113, metadata='
         '{op_name="jit(_spec_shard)/shard_map/hvd_exchange/psum"}')
_HLO = f"""HloModule jit__spec_shard, is_scheduled=true

%fused_computation.1250 (param_0.4154: f32[2048,3,16,128]) -> (f32[2048,3,16,128], s32[2]) {{
  %param_0.4154 = f32[2048,3,16,128]{{3,0,2,1:T(8,128)}} parameter(0)
  %all-reduce.158 = f32[2048,3,16,128]{{3,0,2,1:T(8,128)}} all-reduce(%param_0.4154){_TAIL}
}}

%async_collective_fusion.1049 (param_0.4159: f32[2048,3,16,128], param_24.2: bf16[8,2048,2048]) -> (f32[2048,3,16,128], f32[2048,3,16,128]) {{
  %param_0.4159 = f32[2048,3,16,128]{{3,0,2,1:T(8,128)}} parameter(0)
  %all-reduce.160 = f32[2048,3,16,128]{{3,0,2,1:T(8,128)S(1)}} all-reduce(%param_0.4159){_TAIL}
  %convolution.71 = f32[2048,3,16,128]{{3,0,2,1:T(8,128)}} convolution(%param_24.2, %param_24.2), dim_labels=bf_io->bf
}}

%fused_computation.1252 (param_0.4162: f32[2048,3,16,128]) -> f32[2048,3,16,128] {{
  %param_0.4162 = f32[2048,3,16,128]{{3,0,2,1:T(8,128)}} parameter(0)
  ROOT %all-reduce.162 = f32[2048,3,16,128]{{3,0,2,1:T(8,128)}} all-reduce(%param_0.4162){_TAIL}
}}

ENTRY %main.126_spmd (param.163: f32[2048,3,16,128]) -> f32[2048,3,16,128] {{
  %async-collective-start.1 = (f32[2048,3,16,128]{{3,0,2,1:T(8,128)}}, s32[2]{{0:S(4)}}) fusion(%custom-call.124), kind=kCustom, calls=%fused_computation.1250
  %fusion.1049 = (f32[2048,3,16,128]{{3,0,2,1:T(8,128)}}, f32[2048,3,16,128]{{3,0,2,1:T(8,128)S(1)}}) fusion(%get-tuple-element.1732, %fusion.7), kind=kOutput, calls=%async_collective_fusion.1049, metadata={{op_name="jit(_spec_shard)/shard_map/hvd_backward/dot_general"}}
  %async-collective-done.1 = f32[2048,3,16,128]{{3,0,2,1:T(8,128)}} fusion(%get-tuple-element.1752), kind=kCustom, calls=%fused_computation.1252
  %all-reduce.271 = (f32[16,128,2048]{{2,1,0:T(8,128)}}, f32[16,128,2048]{{2,1,0:T(8,128)}}) all-reduce(%custom-call.161, %custom-call.160), channel_id=2, replica_groups={{{{0,1,2,3}}}}, use_global_device_ids=true, to_apply=%region_95.96, frontend_attributes={{async_collective_name="all-reduce-start.29"}}
  %all-reduce.3 = f32[]{{:T(128)}} all-reduce(%fusion.9), channel_id=3, replica_groups={{{{0,1,2,3}}}}, use_global_device_ids=true, to_apply=%region_1.2
}}
"""


def test_counter_reads_fused_and_bare_all_reduces():
    leaf = 2048 * 3 * 16 * 128 * 4
    pair = 2 * 16 * 128 * 2048 * 4
    got = xla_trace.exchange_async(_HLO)
    assert got == {"all_reduces": 3, "async_all_reduces": 1,
                   "bytes": leaf + pair + 4, "async_bytes": leaf,
                   "async_bytes_share": leaf / (leaf + pair + 4)}
    assert 0.0 < got["async_bytes_share"] < 1.0


def test_counter_reads_a_start_done_pair_as_asynchronous():
    text = _HLO.replace("all-reduce(%custom-call.161",
                        "all-reduce-start(%custom-call.161")
    got = xla_trace.exchange_async(text)
    assert (got["all_reduces"], got["async_all_reduces"]) == (3, 2)


@pytest.mark.parametrize("text", ["", None, "\n".join(
    line for line in _HLO.splitlines() if "all-reduce" not in line)])
def test_counter_reads_nothing_where_nothing_is_all_reduced(text):
    assert xla_trace.exchange_async(text) == {
        "all_reduces": 0, "async_all_reduces": 0, "bytes": 0,
        "async_bytes": 0, "async_bytes_share": 0.0}


@pytest.mark.parametrize("world", [1, 8])
def test_step_publishes_the_counter_once_per_signature(world, monkeypatch):
    """The public path: read from the executable that ran, after the
    first execution of a signature, onto the step object and the
    ``hvd_exchange_*`` gauges; nothing asynchronous on the CPU backend,
    no all-reduce left on one device."""
    hvd.shutdown()  # whatever world an earlier test left
    hvd.init(num_ranks=world)
    try:
        n = hvd.size()
        assert n == world
        params = {"w1": jnp.ones((6, 13)), "b1": jnp.zeros((13,)),
                  "w2": jnp.ones((13, 3))}
        step = hvd.compiled_train_step(
            _loss, hvd.DistributedOptimizer(optax.sgd(0.1)), donate=False)
        state = step.init(params)
        x, y = jnp.ones((2 * n, 6)), jnp.zeros((2 * n, 3))
        assert step.exchange_async is None
        reads = []
        real = xla_trace.exchange_async
        monkeypatch.setattr(xla_trace, "exchange_async",
                            lambda text: reads.append(1) or real(text))
        for _ in range(3):
            params, state, _ = step(params, state, x, y)
        assert len(reads) == 1
        got = step.exchange_async
        assert got["async_all_reduces"] == 0
        assert got["async_bytes_share"] == 0.0
        if n == 1:
            assert got["all_reduces"] == 0
        else:
            grad_bytes = 4 * sum(a.size for a in jax.tree.leaves(params))
            assert got["all_reduces"] >= 1
            assert got["bytes"] == grad_bytes + 4  # + the loss's pmean
        snap = hvd.metrics_snapshot()
        assert (snap["hvd_exchange_all_reduces"]["values"][""]
                == got["all_reduces"])
        assert snap["hvd_exchange_async_bytes_share"]["values"][""] == 0.0
    finally:
        hvd.shutdown()
