"""The main path's kernels — flash attention and the sparse layers' grouped
matmul — compile for a described v5e (Mosaic + the XLA TPU compiler, no chip
attached) at the benchmark cells' shapes; and the compiled step, built for
the four described chips of a v5e:2x2, comes out of the compiler with
gradient all-reduces fused into the backward's matmuls (ops/step_program.py
``_exchange_compiler_options``).

Interpret mode cannot see what the chip's compiler refuses — a slice off
the (8, 128) tiling, more VMEM than a kernel may take — and these compiles
can (/opt/skills/guides/on-chip-measurement §2). Nothing runs, so this says
nothing about results or times. One file, one fixture: only the worker that
is handed this file loads libtpu.
"""

import logging
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import horovod_tpu as hvd
from horovod_tpu.diag import xla_trace
from horovod_tpu.models import moe
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import step_program
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.ops.grouped_matmul import grouped_matmul
from horovod_tpu.utils.logging import get_logger


@pytest.fixture(scope="module")
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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
    # granite4h-micro_s16k: the one attention layer, heads of 64 (half
    # the 128 lanes), 4 query heads a kv head, the scale the model states
    "granite_full": (1, 16384, 32, 8, 64, None),
    # lfm2-24b-a2b_s16k: the same heads at the default scale 1 / 8 (q and
    # k arrive normed and rotated; the kernels do not see that)
    "lfm2_full": (1, 16384, 32, 8, 64, None),
    # kimi-linear_s16k: the latent-attention layer, q and k of 192 (one
    # and a half lane tiles) beside v of 128 (V_SIZES), every head its key
    "kimi_mla": (1, 16384, 32, 32, 192, None),
}
# the factor on q.k where it is not 1 / sqrt(D)
SCALES = {"granite_full": 0.015625}
# v's head size where it is not q's and k's
V_SIZES = {"kimi_mla": 128}


def _shapes(cell, sharding):
    """``(q, k, v, window)`` of a cell's attention layer."""
    b, s, h, h_kv, d, window = CELLS[cell]
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=sharding)
    k, v = (jax.ShapeDtypeStruct((b, s, h_kv, width), jnp.bfloat16,
                                 sharding=sharding)
            for width in (d, V_SIZES.get(cell, d)))
    return q, k, v, window


@pytest.mark.parametrize("cell", CELLS)
def test_flash_forward_compiles_for_v5e(one_chip, cell):
    q, k, v, window = _shapes(cell, one_chip)
    text = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, True, 512, False, window, SCALES.get(cell))).lower(
            q, k, v).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("cell", CELLS)
def test_flash_vjp_compiles_for_v5e(one_chip, cell):
    """Forward + dQ + dK/dV: the three Mosaic calls of one layer's
    attention in the train step."""
    q, k, v, window = _shapes(cell, one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, True, 512, False, window,
                              SCALES.get(cell))
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, k, v).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_kda_kernels_compile_for_v5e(one_chip):
    """kimi-linear_s16k's recurrence, 32 heads of 128 over 16,384
    positions in bf16: the forward kernel (as the forward pass calls it)
    and, under the gradient, the forward kernel that saves the states and
    the backward kernel — strided row blocks, 64 x 64 transposes and the
    backward's 25 MiB of VMEM are what the interpreter does not see."""
    from horovod_tpu.ops import kda_scan
    b, l, h, d = 1, 16384, 32, 128

    def like(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (like((b, l, h * d), jnp.bfloat16),) * 4 + (
        like((b, l, h), jnp.float32), like((h,), jnp.float32),
        like((h * d,), jnp.float32))

    def scan(*a):
        with jax.named_scope("hvd_kda_scan"):
            return kda_scan.kda_scan(*a)

    def loss(*a):
        o, state = scan(*a)
        return o.astype(jnp.float32).sum() + state.sum()

    # the compiled op_name joins the caller's scopes and the kernel's
    # name, forward and backward: what dev_kda_scan_ms, kda_fwd_ms and
    # kda_bwd_ms select on
    for fn, names in ((scan, [("_forward", "hvd_kda_fwd")]),
                      (jax.grad(loss, argnums=tuple(range(7))),
                       [("_forward", "hvd_kda_fwd"),
                        ("_backward", "hvd_kda_bwd")])):
        text = jax.jit(fn).lower(*args).compile().as_text()
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line]
        assert len(calls) == len(names)
        for call, kernel in names:   # under autodiff: jvp(hvd_kda_scan)
            assert sum(bool(re.search(
                rf"hvd_kda_scan\)*/jit\({call}\)/{kernel}/", line))
                for line in calls) == 1


# (rows, contraction, columns): laguna-s21_s8k's expert matrices over one
# chunk of sorted rows (moe.chunk_rows at 16,384 tokens), 8 experts, bf16;
# lfm2-24b-a2b_s16k's: a chunk of 16,384 rows, experts 1536 wide (12 lane
# tiles)
@pytest.mark.parametrize("shape", [(10240, 3072, 1024),
                                   (10240, 1024, 3072),
                                   (16384, 2048, 1536),
                                   (16384, 1536, 2048)])
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


# ------------------------------------------------ the step across four chips

# Cerebras-GPT-1.3B's widths (cgpt13b_dp4), two layers, seq 512
_STEP_CFG = tfm.TransformerConfig(
    vocab_size=50257, d_model=2048, n_heads=16, n_layers=2, d_ff=8192,
    max_seq=512, dtype=jnp.bfloat16, attention_impl="flash",
    flash_interpret=False, positional="learned", loss_chunk=512)


def _shaped(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def _compile_step(mesh, tx, spec=None, state=None, cfg=_STEP_CFG,
                  tokens=None):
    """``_build_step_program``'s program for ``mesh``, lowered on shapes
    and compiled for its described chips (``tokens``: the global batch's
    shape, two sequences of 512 a chip unless given)."""
    axes = tfm.ShardAxes(dp=None, sp=None, tp=None)

    def loss_fn(p, tokens, targets):
        return tfm.loss_fn(p, tokens, targets, cfg, axes)

    rep = NamedSharding(mesh, P())
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    if state is None:
        state = jax.eval_shape(tx.init, params)
    tok = jax.ShapeDtypeStruct(tokens or (2 * mesh.size, 512), jnp.int32,
                               sharding=NamedSharding(mesh, P("hvd")))
    prog = step_program._build_step_program(
        mesh, loss_fn, tx, 2, "psum", True, None, False, True, False, None,
        1, spec)
    return prog.lower(_shaped(params, rep), _shaped(state, rep), tok,
                      tok).compile()


def test_step_on_four_chips_fuses_all_reduces_with_the_backward(topo):
    mesh = Mesh(np.array(topo.devices[:4]), ("hvd",))
    options = step_program._exchange_compiler_options(mesh, "psum")
    assert options == dict(step_program._ASYNC_ALL_REDUCE
                           + (step_program._COMBINER_THRESHOLD,))
    text = _compile_step(mesh, optax.adamw(3e-4)).as_text()
    fused = [block for block in text.split("\n\n")
             if block.lstrip("%").startswith("async_collective_fusion")
             and " all-reduce(" in block]
    assert fused
    got = xla_trace.exchange_async(text)
    assert got["async_all_reduces"] == len(fused)
    assert 0.0 < got["async_bytes_share"] <= 1.0
    grad_bytes = 4 * sum(int(np.prod(a.shape)) for a in jax.tree.leaves(
        jax.eval_shape(lambda k: tfm.init_params(k, _STEP_CFG),
                       jax.random.PRNGKey(0))))
    assert got["bytes"] == grad_bytes + 4  # + the loss's pmean


def test_step_on_one_chip_is_the_bare_jit(topo):
    mesh = Mesh(np.array(topo.devices[:1]), ("hvd",))
    assert step_program._exchange_compiler_options(mesh, "psum") == {}
    text = _compile_step(mesh, optax.adamw(3e-4)).as_text()
    assert " all-reduce" not in text
    assert "async_collective_fusion" not in text
    assert xla_trace.exchange_async(text)["all_reduces"] == 0


def _convolutions(text):
    """``(line, result dimensions, operand types)`` of every convolution of
    an optimized HLO text, a type written ``f32[512,8192]``."""
    types = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]*)\]", line)
        if not m:
            if line.startswith("}"):
                types = {}  # names are per computation
            continue
        name, dtype, dims = m.groups()
        types[name] = f"{dtype}[{dims}]"
        call = re.search(r" convolution\(([^)]*)\)", line)
        if call:
            yield line, [int(d) for d in dims.split(",")], [
                types.get(o) for o in re.findall(r"%([\w.\-]+)",
                                                 call.group(1))]


def _wide_float32_pairs(text, least=2 ** 22):
    """Convolutions of an optimized HLO text under ``hvd_backward``, the
    head's aside, with ``least`` output elements or more and float32 on
    both sides."""
    return [line.split(" = ")[0].strip()
            for line, dims, operands in _convolutions(text)
            if "hvd_backward" in line and "hvd_head_ce" not in line
            and np.prod(dims) >= least
            and all(o and o.startswith("f32[") for o in operands)]


def test_gated_ffn_backward_feeds_no_wide_matmul_two_float32_operands(topo):
    """The regression PR 32 removed (PERF.md section 6): autodiff of the
    gated FFN fused the gate's float32 cotangents into the ``w1`` / ``w3``
    weight gradients, whose other operand, the normed input, is float32
    too — the step's only wide matmuls with float32 on both sides, at a
    third of the matmul unit's rate (lost to the producer evaluated inside
    the convolution, not to the operands' type: float32 on both sides is
    the mark it leaves in the HLO). granite-4.0-h-micro's FFN widths, one
    layer under remat; the parent commit's step has two here."""
    cfg = tfm.TransformerConfig(
        vocab_size=4096, d_model=2048, n_heads=16, n_kv_heads=4, n_layers=1,
        d_ff=8192, max_seq=512, dtype=jnp.bfloat16, attention_impl="flash",
        flash_interpret=False, positional="rope", loss_chunk=512,
        mlp_gated=True, remat=True)
    mesh = Mesh(np.array(topo.devices[:1]), ("hvd",))
    text = _compile_step(mesh, optax.adamw(3e-4), cfg=cfg).as_text()
    assert "hvd_ffn_gate" in text
    assert _wide_float32_pairs(text) == []


@pytest.mark.parametrize("batch, token_operand", [
    (1, "bf16[4,512,8192]"), (4, "f32[4,512,8192]")],
    ids=["one-sequence-g4", "four-sequences-g1"])
def test_head_gradient_is_one_product_a_group_of_chunks(topo, batch,
                                                        token_operand):
    """The head's weight gradient of the 16k cells' loss (PERF.md section
    6 PR 36): ONE sequence in chunks of 512 positions. The scan's own
    transpose made a product a chunk into the whole float32 (d, V)
    gradient — its token operand ``f32[512,8192]`` on the parent commit,
    4 products a step here; ``_grouped_nll`` makes one over the group's
    4 x 512 tokens from the cotangents stored in bfloat16. At four
    sequences a chunk holds 4 x 512 tokens already: the plain scan's
    product a chunk from the float32 cotangent, as on the parent. (The
    compiled text writes no trip count: the tokens a product contracts
    over say how many a step makes.)"""
    cfg = tfm.TransformerConfig(
        vocab_size=8192, d_model=1024, n_heads=8, n_layers=1, d_ff=2048,
        max_seq=2048, dtype=jnp.bfloat16, attention_impl="flash",
        flash_interpret=False, positional="rope", loss_chunk=512)
    mesh = Mesh(np.array(topo.devices[:1]), ("hvd",))
    text = _compile_step(mesh, optax.adamw(3e-4), cfg=cfg,
                         tokens=(batch, 2048)).as_text()
    # the products into the (d, V) gradient, dimensions of 1 aside
    made = [operands for _, dims, operands in _convolutions(text)
            if [d for d in dims if d != 1] == [1024, 8192]]
    assert len(made) == 1 and token_operand in made[0], made


def test_step_with_striped_state_compiles_on_four_chips(topo):
    """``zero_stage=1``: the flat stripe's reduce-scatter and all-gather
    carry the exchange, under the same options. A quarter of the width:
    at Cerebras' the flat stripe takes XLA eight minutes to compile,
    with or without the options (PERF.md section 6 PR 30)."""
    cfg = tfm.TransformerConfig(
        vocab_size=8192, d_model=512, n_heads=4, n_layers=2, d_ff=2048,
        max_seq=512, dtype=jnp.bfloat16, attention_impl="flash",
        flash_interpret=False, positional="learned", loss_chunk=512)
    hvd.shutdown()  # a world an earlier file of this worker may have left
    hvd.init(num_ranks=4)
    try:
        tx = hvd.DistributedOptimizer(optax.adamw(3e-4), zero_stage=1)
        state = jax.eval_shape(tx.init, jax.eval_shape(
            lambda k: tfm.init_params(k, cfg), jax.random.PRNGKey(0)))
    finally:
        hvd.shutdown()
    mesh = Mesh(np.array(topo.devices[:4]), ("hvd",))
    assert step_program._exchange_compiler_options(mesh, "psum")
    text = _compile_step(mesh, tx, tx.update._hvd_spec, state,
                         cfg).as_text()
    assert xla_trace.exchange_async(text)["all_reduces"] >= 1


def test_step_with_expert_leaves_compiles_on_four_chips(topo):
    """An expert-keys spec on the (data, expert) mesh: expert leaves
    all-reduce over the data axis only, dense leaves over all four."""
    cfg = moe.MoEConfig(d_model=1024, d_ff=4096, num_experts=8, top_k=2,
                        capacity_factor=2.0, dtype=jnp.bfloat16)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("hvd", "ep"))

    def loss_fn(p, x, y):
        out, aux = moe.moe_layer(p, x, cfg, ep_axis="ep")
        return jnp.mean((out.astype(jnp.float32) - y) ** 2) + 0.01 * aux

    tx = hvd.DistributedOptimizer(optax.adamw(3e-4),
                                  expert_keys=("w1", "w2"))
    full = jax.eval_shape(lambda k: moe.init_moe_params(k, cfg),
                          jax.random.PRNGKey(0))
    held = {k: (jax.ShapeDtypeStruct((a.shape[0] // 2,) + a.shape[1:],
                                     a.dtype) if k in ("w1", "w2") else a)
            for k, a in full.items()}
    rep = NamedSharding(mesh, P())
    x = jax.ShapeDtypeStruct((16, 512, cfg.d_model), jnp.float32,
                             sharding=NamedSharding(mesh, P(("hvd", "ep"))))
    base = tx.update._hvd_base
    prog = step_program._build_step_program(
        mesh, loss_fn, base, 2, "psum", True, None, False, True, False,
        None, 1, tx.update._hvd_spec)
    text = prog.lower(_shaped(held, rep),
                      _shaped(jax.eval_shape(base.init, held), rep), x,
                      x).compile().as_text()
    assert xla_trace.exchange_async(text)["all_reduces"] >= 2


def test_compiler_is_asked_once_whether_it_knows_an_internal_option(topo):
    """The combiner's threshold is an internal name: this libtpu knows
    it; one it does not know is answered "no" with one line in the log,
    and the step then compiles with the two public options alone."""
    device = topo.devices[0]
    assert step_program._compiler_accepts(
        device, step_program._COMBINER_THRESHOLD)
    said = []
    handler = logging.Handler()
    handler.emit = lambda record: said.append(record.getMessage())
    logger = get_logger()
    logger.addHandler(handler)
    try:
        for _ in range(2):
            assert not step_program._compiler_accepts(
                device, ("xla_jf_no_such_option_in_any_libtpu", 1))
    finally:
        logger.removeHandler(handler)
    assert len(said) == 1 and "no compile option" in said[0]
