"""The device names inside the model (models/transformer.py ``hvd_embed``,
``hvd_attn_proj``, ``hvd_ffn``, ``hvd_block_io``) and the six benchmark
metrics that read them (benchmark/layer_metrics/dev_*_ms.json): the
names are on the instructions of the compiled forward + backward of every
kind of model the benchmark runs, they move no name an older metric
reads, and each metric's pattern hits its name and nothing else
(docs/diagnostics.md "Device scopes"; PERF.md section 3)."""

import contextlib
import functools
import glob
import importlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark.lib import cells
from horovod_tpu.diag.xla_trace import build_op_table, scope_path
from horovod_tpu.models import transformer as tfm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the cells the six metrics list: a cell added since (PERF.md section 7
# names it for a benchmark PR) may not be appended by its own PR
ALL_CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]
             if w["name"] != "lfm2-24b-a2b_s16k"]
NEW_NAMES = ("hvd_attn_proj", "hvd_ffn", "hvd_embed", "hvd_block_io")
NEW_METRICS = {
    "dev_attn_proj_ms": [c for c in ALL_CELLS if c != "kimi-linear_s16k"],
    "dev_ffn_ms": ALL_CELLS,
    "dev_ffn_gate_ms": ["laguna-s21_s8k", "granite4h-micro_s16k",
                        "kimi-linear_s16k"],
    "dev_block_io_ms": ALL_CELLS,
    "dev_embed_ms": ALL_CELLS,
    "dev_trunk_unnamed_ms": ALL_CELLS,
}
# every device name the program opens (jax.named_scope, pallas_call name):
# test_every_scope_of_the_program_is_listed keeps the list whole
REGIONS = ("hvd_forward", "hvd_backward", "hvd_exchange", "hvd_optimizer",
           "hvd_guard")
FINER = NEW_NAMES + (
    "hvd_ffn_gate", "hvd_head_ce", "hvd_attn_full", "hvd_attn_window",
    "hvd_mla_proj", "hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv",
    "hvd_flash_band_fwd", "hvd_flash_band_dq", "hvd_flash_band_dkv",
    "hvd_gmm", "hvd_moe", "hvd_moe_route", "hvd_moe_dispatch",
    "hvd_moe_experts", "hvd_moe_combine", "hvd_moe_shared", "hvd_dispatch",
    "hvd_expert", "hvd_combine", "hvd_ssm", "hvd_ssm_in_proj",
    "hvd_ssm_conv", "hvd_ssm_scan", "hvd_ssm_norm", "hvd_ssm_out_proj",
    "hvd_kda", "hvd_kda_in_proj", "hvd_kda_conv", "hvd_kda_scan",
    "hvd_kda_norm", "hvd_kda_out_proj", "hvd_kda_fwd", "hvd_kda_bwd",
    "hvd_conv_fwd", "hvd_conv_bwd", "hvd_sconv", "hvd_sconv_in_proj", "hvd_sconv_gate",
    "hvd_sconv_out_proj", "hvd_qk_norm", "hvd_ici", "hvd_dcn",
    "hvd_prefill", "hvd_decode")


@functools.lru_cache(maxsize=None)
def _reader(name):
    return cells.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", f"{name}.json"))


@functools.lru_cache(maxsize=None)
def _old_scope_patterns():
    """``{metric: pattern}`` of the trace_scope metric files older than
    this file's six."""
    out = {}
    for path in sorted(glob.glob(os.path.join(
            ROOT, "benchmark", "layer_metrics", "*.json"))):
        name = os.path.basename(path)[:-len(".json")]
        reader = cells.load_json(path)
        if reader["source"] == "trace_scope" and name not in NEW_METRICS:
            out[name] = reader["pattern"]
    return out


# ------------------------------------------------ (d) the six metric files

@pytest.mark.parametrize("cell", ALL_CELLS)
@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_metric_loads_for_the_cells_on_its_list_and_no_other(metric, cell):
    loaded = {m["name"]: m for m in cells.metrics_for("per_layer", cell)}
    assert (metric in loaded) == (cell in NEW_METRICS[metric])
    if metric in loaded:
        m = loaded[metric]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("ms", "lower", "device_trace", "model",
                                "tokens_per_s_per_chip")
        assert m["workloads"] == NEW_METRICS[metric]
        assert (m["reader"]["source"], m["reader"]["reduce"]) == (
            "trace_scope", "sum_ms_per_step")


def _paths(name):
    """The op_names an instruction under ``name`` carries: forward, the
    transposed backward, remat's second forward."""
    return (f"jit(step)/hvd_forward/jvp({name})/dot_general",
            f"jit(step)/hvd_forward/{name}/mul",
            f"jit(step)/hvd_backward/transpose(jvp(hvd_forward))/jvp()/"
            f"checkpoint/{name}/transpose",
            f"jit(step)/hvd_backward/transpose(jvp(hvd_forward))/jvp()/"
            f"checkpoint/rematted_computation/{name}/add")


@pytest.mark.parametrize("metric,own", [
    ("dev_attn_proj_ms", "hvd_attn_proj"), ("dev_ffn_ms", "hvd_ffn"),
    ("dev_ffn_gate_ms", "hvd_ffn_gate"), ("dev_block_io_ms", "hvd_block_io"),
    ("dev_embed_ms", "hvd_embed")])
@pytest.mark.parametrize("name", FINER)
def test_pattern_hits_its_name_and_misses_every_other(metric, own, name):
    rx = re.compile(_reader(metric)["pattern"])
    # hvd_ffn_gate is opened inside hvd_ffn and is part of it
    want = name == own or (own == "hvd_ffn" and name == "hvd_ffn_gate")
    for path in _paths(name):
        assert bool(rx.search(path)) == want, path


@pytest.mark.parametrize("name", FINER)
def test_trunk_unnamed_misses_every_finer_name(name):
    rx = re.compile(_reader("dev_trunk_unnamed_ms")["pattern"])
    for path in _paths(name):
        assert not rx.search(path), path
    assert not rx.search(
        f"jit(step)/hvd_backward/hvd_ffn/jvp({name})/hvd_optimizer/mul")


@pytest.mark.parametrize("path,want", [
    ("jit(step)/hvd_forward/jvp()/add", True),
    ("jit(step)/hvd_forward/jvp()/concatenate", True),
    ("jit(step)/hvd_backward/transpose(jvp(hvd_forward))/jvp()/remat2", True),
    # a weight gradient with adamw as epilogue: still the model's
    ("jit(step)/hvd_backward/hvd_optimizer/mul", True),
    ("jit(step)/hvd_backward/hvd_exchange_bucket3/psum", True),
    # outside forward and backward: not the trunk's to name
    ("jit(step)/hvd_optimizer/mul", False),
    ("jit(step)/hvd_exchange/hvd_ici/psum", False),
    ("jit(step)/hvd_guard/is_finite", False),
    ("jit(step)/transpose/neg", False), ("", False),
    # a region's name as the prefix of another name is that other name
    ("jit(step)/hvd_forward/hvd_forward_extra/mul", False),
    ("jit(step)/hvd_backward/hvd_guarded/mul", False),
])
def test_trunk_unnamed_truth_table(path, want):
    rx = re.compile(_reader("dev_trunk_unnamed_ms")["pattern"])
    assert bool(rx.search(path)) == want


@pytest.mark.parametrize("metric", sorted(_old_scope_patterns()))
@pytest.mark.parametrize("name", NEW_NAMES)
def test_new_name_moves_no_older_metric(metric, name):
    """An instruction counts for an older metric with the new name on
    its path exactly when it counted without it."""
    rx = re.compile(_old_scope_patterns()[metric])
    for path in _paths(name) + (
            f"jit(step)/hvd_backward/{name}/hvd_optimizer/mul",
            f"jit(step)/hvd_forward/hvd_kda/{name}/mul"):
        without = re.sub(rf"jvp\({name}\)/|{name}/", "", path)
        assert name not in without
        assert bool(rx.search(path)) == bool(rx.search(without)), path


def test_every_scope_of_the_program_is_listed():
    found = set()
    for path in glob.glob(os.path.join(ROOT, "horovod_tpu", "**", "*.py"),
                          recursive=True):
        with open(path, encoding="utf-8") as f:
            found.update(re.findall(
                r'(?:named_scope|name=|_named_pallas_call)\(?\s*f?"'
                r'(hvd_[a-z0-9_]+)"', f.read()))
    found = {n for n in found if not n.startswith("hvd_exchange_bucket")}
    assert found <= set(FINER) | set(REGIONS), found - set(FINER)
    assert set(NEW_NAMES) <= found


# --------------------------- (c) the names on the compiled forward/backward

PRESETS = {"tiny.json": "sc2-3b_s4k", "tiny_laguna.json": "laguna-s21_s8k",
           "tiny_granite.json": "granite4h-micro_s16k",
           "tiny_kimi_linear.json": "kimi-linear_s16k",
           "tiny_lfm2.json": "lfm2-24b-a2b_s16k"}
# One family of names holds each instruction of the model: the family
# metrics add up to forward + backward less dev_trunk_unnamed_ms.
FAMILIES = NEW_NAMES + ("hvd_head_ce", "hvd_attn_full", "hvd_attn_window",
                        "hvd_mla_proj", "hvd_moe", "hvd_ssm", "hvd_kda",
                        "hvd_sconv", "hvd_qk_norm")
# What carries no finer name, by the last component of its op_name: the
# loss's scalar arithmetic, the stacking of the layers' statistics, remat's
# own call, and at toy widths only (a head of 16 is no 128-lane tile) the
# XLA form of the KDA recurrence, whose checkpointed loop body loses the
# names around it when it is transposed.
UNNAMED_OK = {"add", "mul", "div", "concatenate", "remat2"}
UNNAMED_OK_XLA_KDA = UNNAMED_OK | {
    "slice", "neg", "exp", "broadcast_in_dim", "jit(tril)", "rem",
    "jit(_where)", "squeeze", "sub", "select_n", "transpose", "reshape"}


@pytest.fixture
def no_compile_cache():
    """The persistent cache's key leaves metadata out: a hit would hand
    back the names of whichever program was compiled first."""
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _step_hlo(preset):
    """Optimized HLO of forward + backward of ``tfm.loss_and_stats`` at a
    rehearsal preset's sizes, scoped as ops/step_program.py scopes them."""
    cell = cells.load_cell(PRESETS[preset])
    mode = importlib.import_module(
        "benchmark.modes." + cell["cell"]["mode"])
    cell = mode.apply_tiny(cell, cells.load_json(os.path.join(
        ROOT, "benchmark", "tests", preset)))
    cfg = mode.model_config(cell, interpret=True)
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct(
        (cell["traffic"]["global_batch"] // cell["chips"],
         cell["traffic"]["seq_len"]), jnp.int32)

    def step(p, tok, tgt):
        with jax.named_scope("hvd_forward"):
            loss, bwd, aux = jax.vjp(
                lambda p: tfm.loss_and_stats(p, tok, tgt, cfg), p,
                has_aux=True)
        with jax.named_scope("hvd_backward"):
            (grads,) = bwd(jnp.ones_like(loss))
        return loss, grads, aux

    jax.clear_caches()
    return jax.jit(step).lower(params, tokens, tokens).compile(
        compiler_options={"xla_backend_optimization_level": 0,
                          "xla_llvm_disable_expensive_passes": True}
    ).as_text()


@pytest.mark.parametrize("preset", sorted(PRESETS))
def test_every_instruction_of_the_model_carries_a_finer_name(
        preset, no_compile_cache):
    table = build_op_table(_step_hlo(preset))
    named = [op for op in table.values()
             if re.search(r"hvd_(forward|backward)", op.op_name)]
    assert len(named) > 1000
    ok = UNNAMED_OK_XLA_KDA if "kimi" in preset else UNNAMED_OK
    unnamed = [op for op in named if scope_path(op.op_name).count("/") == 0]
    assert {op.op_name.rsplit("/", 1)[-1] for op in unnamed} <= ok
    assert len(unnamed) <= len(named) // 100
    matmuls = [op for op in named if op.opcode in ("dot", "convolution")]
    assert len(matmuls) >= 40
    assert not [op.op_name for op in matmuls if op in unnamed]
    # no instruction is under two families: the family metrics add up
    for op in named:
        labels = set(re.findall(r"hvd_[a-z0-9_]+", op.op_name))
        assert len(labels & set(FAMILIES)) <= 1, op.op_name
    want = set(NEW_NAMES) - (
        {"hvd_attn_proj"} if "kimi" in preset else set())
    assert want <= {label for op in named for label in
                    re.findall(r"hvd_[a-z0-9_]+", op.op_name)}


@pytest.mark.parametrize("preset", ["tiny.json", "tiny_granite.json"])
def test_new_names_leave_the_program_and_the_older_names_alone(
        preset, no_compile_cache, monkeypatch):
    """Compiled without the four names, the step is the same program
    instruction for instruction, and every older metric's pattern
    matches on the same instructions."""
    with_names = build_op_table(_step_hlo(preset))
    scope = jax.named_scope
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
        if name in NEW_NAMES else scope(name))
    without = build_op_table(_step_hlo(preset))
    assert list(with_names) == list(without)
    patterns = [re.compile(p) for p in _old_scope_patterns().values()]
    changed = 0
    for name, op in with_names.items():
        old = without[name]
        assert (op.opcode, op.shape, op.operands) == (
            old.opcode, old.shape, old.operands)
        changed += op.op_name != old.op_name
        for rx in patterns:
            assert bool(rx.search(op.op_name)) == bool(
                rx.search(old.op_name)), (rx.pattern, op.op_name)
    assert changed > 100
