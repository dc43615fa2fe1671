"""On-demand XLA device tracing (diag/xla_trace.py): the HLO op_name
phase join (first step-region label wins), the xplane reader's tolerance
of missing and malformed captures, the reduction on plain event lists
and on the v5e recording the benchmark keeps, the end-to-end
compiled-step window, the inert-by-default contract, and the diag CLI
--xla-trace merge (docs/diagnostics.md "Seeing inside the compiled
step")."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.diag import xla_trace
from horovod_tpu.diag.xla_trace import (StepTracer, build_op_table,
                                        clock_map, kernel_of_op_name,
                                        matmul_flops, op_class,
                                        parse_trace_dir, phase_of_op_name,
                                        read_capture, scope_path,
                                        shape_bytes, stage_of_op_name,
                                        summarize)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "benchmark", "tests", "data")

SYNTH_HLO = """
  %dot.1 = f32[4,4]{1,0} dot(%p0, %p1), metadata={op_name="jit(step)/jit(main)/hvd_forward/dot_general" source_file="m.py"}
  %add.2 = f32[4]{0} add(%a, %b), metadata={op_name="jit(step)/hvd_exchange/hvd_ici/psum/add"}
  %mul.3 = f32[4]{0} multiply(%c, %d), metadata={op_name="jit(step)/hvd_exchange/hvd_dcn/psum-scatter"}
  %neg.4 = f32[4]{0} negate(%e), metadata={op_name="jit(step)/transpose/neg"}
  %copy.5 = f32[4]{0} copy(%f)
  %while.6 = (s32[], f32[4]{0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/hvd_forward/while"}
  %kern.7 = bf16[2,8,128]{2,1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/hvd_backward/transpose(jvp(hvd_forward))/hvd_flash_dq/pallas_call"}
  %ar.8 = f32[1024]{0} all-reduce(%g), replica_groups={}, to_apply=%sum, metadata={op_name="jit(step)/hvd_exchange/psum"}
  %ars.9 = (f32[256]{0}, f32[256]{0}) all-reduce-start(%h, %i), to_apply=%sum, metadata={op_name="jit(step)/hvd_exchange/psum"}
  %ard.10 = (f32[256]{0}, f32[256]{0}) all-reduce-done(%ars.9), metadata={op_name="jit(step)/hvd_exchange/psum"}
"""


def _lane(ops, modules=(), asyncs=()):
    """One lane of ``read_capture``'s plain lists; times given in us."""
    def rows(evs):
        out = [[name, ts * 1000, dur * 1000, None] for name, ts, dur in evs]
        return sorted(out, key=lambda r: (r[1], -r[2]))
    return {"ops": rows(ops), "async": rows(asyncs),
            "modules": [[n, ts * 1000, dur * 1000] for n, ts, dur in modules]}


def _events(**lanes):
    return {"lanes": lanes, "host": [], "files": []}


def test_phase_of_op_name_first_region_wins():
    assert phase_of_op_name("jit(f)/hvd_forward/dot") == "forward"
    # a custom-vjp kernel's backward and remat's recomputed forward carry
    # the forward's label further down the path: both are backward time
    assert phase_of_op_name(
        "jit(f)/hvd_backward/transpose(hvd_forward)/pallas_call") \
        == "backward"
    assert phase_of_op_name(
        "jit(f)/hvd_backward/transpose(jvp(hvd_forward))/jvp()/checkpoint/"
        "rematted_computation/hvd_flash_fwd/pallas_call") == "backward"
    assert phase_of_op_name(
        "jit(f)/hvd_optimizer/hvd_exchange/psum") == "optimizer"
    # MoE sub-phases are leaves inside forward/backward: innermost wins
    assert phase_of_op_name(
        "jit(f)/hvd_forward/hvd_dispatch/all_to_all") == "dispatch"
    assert phase_of_op_name(
        "jit(f)/hvd_backward/transpose(hvd_forward)/hvd_expert/dot") \
        == "expert"
    assert phase_of_op_name("jit(f)/hvd_exchange_bucket3/psum") == "exchange"
    assert phase_of_op_name("jit(f)/transpose/neg") is None
    assert phase_of_op_name(None) is None
    assert stage_of_op_name("jit(f)/hvd_exchange/hvd_dcn/psum") == "dcn"
    assert stage_of_op_name("jit(f)/hvd_exchange/psum") is None
    assert kernel_of_op_name(
        "jit(f)/hvd_backward/transpose(hvd_forward)/hvd_flash_dq/"
        "hvd_flash_dq/pallas_call") == "hvd_flash_dq"
    assert kernel_of_op_name("jit(f)/hvd_forward/pallas_call") is None


def test_build_op_table_synthetic_hlo():
    table = build_op_table(SYNTH_HLO)
    assert table["dot.1"].op_name.endswith("hvd_forward/dot_general")
    # an instruction the compiler made without metadata has no op_name
    assert sum(bool(op.op_name) for op in table.values()) == 9
    assert build_op_table("") == {}
    assert table["copy.5"][:3] == ("copy", "f32[4]{0}", "")
    assert table["dot.1"].operands == ("p0", "p1")
    assert table["kern.7"].attrs == {
        "custom_call_target": "tpu_custom_call"}
    assert table["kern.7"][0] == "custom-call"
    assert table["ars.9"][0] == "all-reduce-start"
    assert shape_bytes(table["ar.8"][1]) == 4096
    assert shape_bytes("(f32[256]{0}, bf16[2,8]{1,0:T(8,128)})") \
        == 1024 + 32
    assert shape_bytes("(f32[8]{0}, f32[32]{0}, u32[])", largest=True) == 128


def _conv_hlo(lhs, rhs, out, attrs, opcode="convolution"):
    """A fused computation holding one matmul, and the fusion calling it."""
    return f"""
%fused_mm (p0: {lhs}, p1: {rhs}) -> {out} {{
  %p0 = {lhs}{{1,0}} parameter(0)
  %p1 = {rhs}{{1,0}} parameter(1)
  ROOT %mm.1 = {out}{{1,0}} {opcode}(%p0, %p1), {attrs}, metadata={{op_name="jit(step)/hvd_forward/hvd_ffn/dot_general"}}
}}

ENTRY %main (a: {lhs}, b: {rhs}) -> {out} {{
  %a = {lhs}{{1,0}} parameter(0)
  %b = {rhs}{{1,0}} parameter(1)
  ROOT %mm_fusion = {out}{{1,0}} fusion(%a, %b), kind=kOutput, calls=%fused_mm, metadata={{op_name="jit(step)/hvd_forward/hvd_ffn/dot_general"}}
}}
"""


@pytest.mark.parametrize("lhs,rhs,out,attrs,want", [
    # the forms the eight cells' step programs show on a v5e
    ("f32[8,16]", "f32[16,32]", "f32[8,32]", "dim_labels=bf_io->bf",
     2 * 8 * 16 * 32),
    ("f32[64,8]", "f32[64,32]", "f32[8,32]", "dim_labels=fb_io->bf",
     2 * 8 * 64 * 32),
    ("bf16[8,16]", "bf16[32,16]", "f32[8,32]", "dim_labels=bf_oi->bf",
     2 * 8 * 16 * 32),
    # a product per head as a window of 32 with nothing padded ...
    ("bf16[16384,32,192]", "bf16[2304,32,192]", "f32[16384,2304,1]",
     "window={size=32}, dim_labels=b0f_o0i->bf0",
     2 * 16384 * 2304 * 32 * 192),
    # ... and as a window of 32 of which 31 taps are padding: what the
    # dense product of the same operands needs, not 32 times that
    ("f32[16384,2304,1]", "bf16[2304,32,192]", "f32[16384,32,192]",
     "window={size=32 pad=31_31 rhs_reversal=1}, dim_labels=bf0_i0o->b0f",
     2 * 16384 * 2304 * 32 * 192),
    # three spatial dimensions, one padded (kimi-linear's 32-wide
    # projection over 256 x 8 x 8 tokens)
    ("bf16[256,8,8,2304,1]", "bf16[4,8,2304,1,1]", "f32[256,8,8,4,8]",
     "window={size=1x1x4 pad=0_0x0_0x3_3 rhs_reversal=0x0x1}, "
     "dim_labels=01bf2_2oi01->01b2f", 2 * 16384 * 2304 * 32),
    # a causal depthwise convolution: the first three positions see
    # 1, 2, 3 taps
    ("bf16[2,100,64]", "bf16[4,1,64]", "bf16[2,100,64]",
     "window={size=4 pad=3_0}, dim_labels=b0f_0io->b0f, "
     "feature_group_count=64", 2 * 2 * 64 * (4 * 100 - 6)),
    ("f32[2,50,64]", "f32[4,64,8]", "f32[2,24,8]",
     "window={size=4 stride=2}, dim_labels=b0f_0io->b0f",
     2 * 2 * 24 * 4 * 64 * 8),
    # a dilated left operand: ten elements at the even positions of 19,
    # each tap meets 9, 8 and 9 of them
    ("f32[2,10,8]", "f32[3,8,8]", "f32[2,17,8]",
     "window={size=3 lhs_dilate=2}, dim_labels=b0f_0io->b0f",
     2 * 2 * 8 * 8 * 26),
    # a batch of 8 written as a window of 8 over an operand dilated by 8
    # at stride 7, so that only chunk c of one side meets chunk c of the
    # other (granite's chunked scan, "convolution-base-dilated")
    ("bf16[8,256,128]", "bf16[8,256,128]", "f32[8,256,256]",
     "window={size=8 stride=7 lhs_dilate=8}, dim_labels=0bf_0oi->0bf",
     2 * 8 * 256 * 256 * 128),
    ("bf16[8,256,64,64]", "bf16[8,64,256,256]", "f32[8,64,64,256]",
     "window={size=8x64 stride=7x63 lhs_dilate=8x64}, "
     "dim_labels=0f1b_01oi->01bf", 2 * 8 * 64 * 256 * 256 * 64),
])
def test_matmul_flops_by_dim_labels(lhs, rhs, out, attrs, want):
    table = build_op_table(_conv_hlo(lhs, rhs, out, attrs))
    assert matmul_flops(table["mm.1"], table) == want
    fusion = table["mm_fusion"]
    assert [op.opcode for op in fusion.body] == ["parameter", "parameter",
                                                 "convolution"]
    assert op_class(fusion) == "matmul"
    s = summarize(_events(l0=_lane([("mm_fusion", 0, 10)])), table,
                  peak_flops=1e12)
    (row,) = s["matmuls"]
    assert row["flops"] == want and row["calls"] == 1
    assert row["scope"] == "forward/hvd_ffn"
    assert row["operands"] == "×".join(
        t.split("[")[0] for t in (lhs, rhs))
    assert row["at_peak_s"] == pytest.approx(want / 1e12)
    assert row["lost_s"] == pytest.approx(10e-6 - want / 1e12)


def test_matmul_flops_dot_and_what_cannot_be_counted():
    for lhs, out, dims, want in (
            ("f32[8,16]", "f32[8,32]",
             "lhs_contracting_dims={1}, rhs_contracting_dims={0}",
             2 * 8 * 32 * 16),
            ("f32[4,8,16]", "f32[4,8,32]",
             "lhs_batch_dims={0}, lhs_contracting_dims={2}, "
             "rhs_batch_dims={0}, rhs_contracting_dims={1}",
             2 * 4 * 8 * 32 * 16)):
        table = build_op_table(_conv_hlo(lhs, "f32[16,32]", out, dims,
                                         opcode="dot"))
        assert matmul_flops(table["mm.1"], table) == want
    # an operand the table does not hold: no count, never a guess
    del table["p1"]
    assert matmul_flops(table["mm.1"], table) is None


CLASS_HLO = """
%fused_copy (p: f32[8,4]) -> f32[4,8] {
  %p = f32[8,4]{1,0} parameter(0)
  %b.1 = f32[8,4]{1,0} bitcast(%p)
  ROOT %t.1 = f32[4,8]{1,0} transpose(%b.1), dimensions={1,0}
}

%fused_elem (p.1: f32[8,4]) -> f32[8,4] {
  %p.1 = f32[8,4]{1,0} parameter(0)
  %c.1 = f32[] constant(2)
  %bc.1 = f32[8,4]{1,0} broadcast(%c.1), dimensions={}
  ROOT %m.1 = f32[8,4]{1,0} multiply(%p.1, %bc.1)
}

%fused_reduce (p.2: f32[8,4]) -> f32[8] {
  %p.2 = f32[8,4]{1,0} parameter(0)
  %e.2 = f32[8,4]{1,0} exponential(%p.2)
  %z.2 = f32[] constant(0)
  ROOT %r.2 = f32[8]{0} reduce(%e.2, %z.2), dimensions={1}, to_apply=%sum
}

%fused_scatter (p.3: f32[8,4], i.3: s32[2,1], u.3: f32[2,4]) -> f32[8,4] {
  %p.3 = f32[8,4]{1,0} parameter(0)
  %i.3 = s32[2,1]{1,0} parameter(1)
  %u.3 = f32[2,4]{1,0} parameter(2)
  ROOT %s.3 = f32[8,4]{1,0} scatter(%p.3, %i.3, %u.3), to_apply=%sum
}

%fused_mm_ar (p.4: f32[8,4], q.4: f32[4,4]) -> f32[8,4] {
  %p.4 = f32[8,4]{1,0} parameter(0)
  %q.4 = f32[4,4]{1,0} parameter(1)
  %g.4 = f32[8,4]{1,0} logistic(%p.4), metadata={op_name="jit(f)/hvd_backward/hvd_ffn/hvd_ffn_gate/logistic"}
  %cv.4 = f32[8,4]{1,0} convolution(%g.4, %q.4), dim_labels=bf_io->bf, metadata={op_name="jit(f)/hvd_backward/hvd_ffn/dot_general"}
  ROOT %ar.4 = f32[8,4]{1,0} all-reduce(%cv.4), replica_groups={{0,1}}, to_apply=%sum, metadata={op_name="jit(f)/hvd_exchange/psum"}
}

ENTRY %main () -> f32[] {
  %x = f32[8,4]{1,0} parameter(0)
  %copy_fusion = f32[4,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_copy
  %elem_fusion = f32[8,4]{1,0} fusion(%x), kind=kLoop, calls=%fused_elem
  %reduce_fusion = f32[8]{0} fusion(%x), kind=kInput, calls=%fused_reduce
  %scatter_fusion = f32[8,4]{1,0} fusion(%x, %i, %u), kind=kLoop, calls=%fused_scatter
  %mm_ar_fusion = f32[8,4]{1,0} fusion(%x, %w), kind=kOutput, calls=%fused_mm_ar, metadata={op_name="jit(f)/hvd_backward/hvd_ffn/dot_general"}
  %dot.9 = f32[8,4]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %conv.9 = f32[8,4]{1,0} convolution(%x, %w), dim_labels=bf_io->bf
  %mosaic.9 = f32[8,4]{1,0} custom-call(%x), custom_call_target="tpu_custom_call"
  %topk.9 = f32[8,4]{1,0} custom-call(%x), custom_call_target="TopK"
  %ag.9 = f32[16,4]{1,0} all-gather(%x), dimensions={0}
  %ars.9 = f32[8,4]{1,0} all-reduce-start(%x), to_apply=%sum
  %ard.9 = f32[8,4]{1,0} all-reduce-done(%ars.9)
  %cs.9 = (f32[8,4]{1,0}, f32[8,4]{1,0}, u32[]) copy-start(%x)
  %ds.9 = f32[2,4]{1,0} dynamic-slice(%x, %z, %z), dynamic_slice_sizes={2,4}
  %sort.9 = f32[8,4]{1,0} sort(%x), dimensions={1}, to_apply=%lt
  %rw.9 = f32[8,1]{1,0} reduce-window(%x, %z), window={size=1x4}, to_apply=%sum
  %tanh.9 = f32[8,4]{1,0} tanh(%x)
  %cvt.9 = bf16[8,4]{1,0} convert(%x)
  %while.9 = (s32[], f32[8,4]{1,0}) while(%t), condition=%c, body=%b
  %gte.9 = f32[8,4]{1,0} get-tuple-element(%while.9), index=1
}
"""


@pytest.mark.parametrize("instr,want", [
    ("mm_ar_fusion", "matmul"), ("dot.9", "matmul"), ("conv.9", "matmul"),
    ("mosaic.9", "kernel"),
    ("ag.9", "collective"), ("ars.9", "collective"), ("ard.9", "collective"),
    ("copy_fusion", "copy"), ("cs.9", "copy"), ("ds.9", "copy"),
    ("scatter_fusion", "gather_scatter"), ("sort.9", "gather_scatter"),
    ("reduce_fusion", "reduce"), ("rw.9", "reduce"),
    ("elem_fusion", "elementwise"), ("tanh.9", "elementwise"),
    ("cvt.9", "elementwise"),
    ("while.9", "other"), ("gte.9", "other"), ("topk.9", "other"),
])
def test_op_class_of_an_instruction_and_of_a_fusion_body(instr, want):
    table = build_op_table(CLASS_HLO)
    assert op_class(table[instr]) == want
    s = summarize(_events(l0=_lane([(instr, 0, 7)])), table)
    path = scope_path(table[instr].op_name)
    assert s["classes"] == {path: {want: pytest.approx(7e-6)}}
    assert s["scopes"] == {path: pytest.approx(7e-6)}


def test_op_class_without_the_table():
    """An instruction the table lacks has its event's text: the opcode,
    and for a custom call the target; a fusion's body it has not."""
    ev = {"lanes": {"tpu:0": {"ops": [
        ["k.1", 0, 5000, '%k.1 = f32[8]{0} custom-call(f32[8]{0} %x), '
                         'custom_call_target="tpu_custom_call"'],
        ["fusion.7", 6000, 2000,
         "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %x), kind=kLoop, "
         "calls=%fused_computation.7"],
        ["copy.3", 9000, 1000, "%copy.3 = f32[8]{0} copy(f32[8]{0} %x)"]],
        "modules": [], "async": []}}, "host": [], "files": []}
    s = summarize(ev)
    assert s["classes"] == {"other": {"kernel": pytest.approx(5e-6),
                                      "other": pytest.approx(2e-6),
                                      "copy": pytest.approx(1e-6)}}
    assert s["matmuls"] == []


def test_matmul_row_rides_and_carried_collective():
    table = build_op_table(CLASS_HLO)
    s = summarize(_events(
        l0=_lane([("mm_ar_fusion", 0, 10), ("mm_ar_fusion", 20, 10)]),
        l1=_lane([("mm_ar_fusion", 0, 12)])), table, peak_flops=1e9)
    (row,) = s["matmuls"]
    assert row["scope"] == "backward/hvd_ffn"
    assert row["carries_collective"] is True
    # the other names on the body's instructions, and its transcendentals
    assert row["rides"] == ["hvd_exchange", "hvd_ffn_gate", "logistic"]
    assert (row["lhs"], row["rhs"], row["result"]) == (
        "f32[8,4]", "f32[4,4]", "f32[8,4]")
    assert row["calls"] == 3 and row["flops"] == 2 * 8 * 4 * 4
    assert row["device_s"] == pytest.approx(32e-6)
    assert row["lost_s"] == pytest.approx(32e-6 - 3 * 256e-9)
    # a device the peak table does not list: the two are left out
    s = summarize(_events(l0=_lane([("mm_ar_fusion", 0, 10)])), table)
    assert "at_peak_s" not in s["matmuls"][0]
    assert "lost_s" not in s["matmuls"][0]


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/hvd_backward/transpose(jvp(hvd_forward))/jvp()/checkpoint/"
     "rematted_computation/hvd_kda/hvd_kda_scan/hvd_kda_fwd/pallas_call",
     "backward/hvd_kda/hvd_kda_scan/hvd_kda_fwd"),
    ("jit(step)/hvd_backward/transpose(jvp(hvd_forward))/hvd_ffn/"
     "transpose(jvp(hvd_ffn))/hvd_ffn_gate/mul",
     "backward/hvd_ffn/hvd_ffn_gate"),
    ("jit(step)/hvd_forward/jvp(hvd_mla_proj)/bsd,dhx->bshx/dot_general",
     "forward/hvd_mla_proj"),
    ("jit(step)/hvd_forward/hvd_moe/hvd_dispatch/all_to_all", "dispatch"
     "/hvd_moe"),
    ("jit(step)/hvd_exchange_bucket3/hvd_ici/psum", "exchange/hvd_ici"),
    ("jit(step)/hvd_optimizer/hvd_exchange/psum", "optimizer"),
    ("jit(step)/transpose/neg", "other"), ("", "other"), (None, "other"),
])
def test_scope_path(op_name, want):
    assert scope_path(op_name) == want
    assert want.split("/")[0] == (phase_of_op_name(op_name) or "other")


def test_parse_trace_dir_missing_empty_malformed(tmp_path):
    # nonexistent and empty directories degrade to "no data"
    assert parse_trace_dir(str(tmp_path / "nope")) is None
    assert parse_trace_dir(str(tmp_path)) is None
    assert parse_trace_dir("") is None
    # a truncated / garbage xplane file never raises
    bad = tmp_path / "bad" / "plugins" / "profile" / "t"
    bad.mkdir(parents=True)
    (bad / "a.xplane.pb").write_bytes(b"\x1f\x8b\x08garbage")
    (bad / "b.xplane.pb").write_bytes(b"")
    assert parse_trace_dir(str(tmp_path / "bad")) is None
    # a capture with host spans only (no device op ran) is "no data" too
    host_only = {"lanes": {}, "host": [["hvd_step", 0, 5, None]],
                 "files": ["x"]}
    assert summarize(host_only) is None
    assert summarize(_events(l0=_lane([]))) is None
    assert summarize(None) is None


def test_summarize_joins_phases():
    s = summarize(_events(
        l0=_lane([("dot.1", 0, 100), ("mul.3", 160, 30),
                  ("neg.4", 200, 25),   # mapped, outside hvd_ scopes
                  ("fusion.9", 230, 5),  # unmapped instruction
                  ("copy.5", 240, 10)]),  # mapped, no metadata
        l1=_lane([("add.2", 120, 50)])), build_op_table(SYNTH_HLO))
    us = 1e-6
    assert s["phases"]["forward"] == pytest.approx(100 * us)
    assert s["phases"]["exchange"] == pytest.approx((50 + 30) * us)
    assert s["phases"]["other"] == pytest.approx((25 + 5 + 10) * us)
    assert s["stages"]["dcn"] == pytest.approx(30 * us)
    assert s["stages"]["ici"] == pytest.approx(50 * us)
    assert s["events"] == 6 and s["lanes"] == 2
    assert s["total_s"] == pytest.approx(sum(s["phases"].values()))
    assert s["ts_min_us"] == 0 and s["ts_max_us"] == 250
    assert s["kernels"] == {} and s["collectives"] == []
    assert s["host"] == {} and s["clock"] is None


def test_summarize_self_time_kernels_and_collectives():
    """An enclosing while counts only what its body does not cover; a
    custom call is filed under its kernel name; a synchronous collective
    is all exposed, an asynchronous one only where no compute ran
    between its start and its done."""
    s = summarize(_events(l0=_lane(
        [("while.6", 0, 100), ("dot.1", 10, 30), ("dot.1", 50, 30),
         ("kern.7", 100, 40), ("kern.7", 140, 40),
         ("ar.8", 200, 50),
         ("ars.9", 300, 1), ("dot.1", 301, 60), ("ard.10", 361, 19)],
        modules=[("jit_step", 0, 400)],
        asyncs=[("ars.9", 300, 80)])), build_op_table(SYNTH_HLO))
    us = 1e-6
    # while: 100 - 60 of body; three dots of 30, 30, 60
    assert s["phases"]["forward"] == pytest.approx((40 + 120) * us)
    assert s["phases"]["backward"] == pytest.approx(80 * us)
    assert s["kernels"] == {"hvd_flash_dq": {"s": pytest.approx(80 * us),
                                             "calls": 2}}
    rows = {(r["op"], r["bytes"]): r for r in s["collectives"]}
    sync = rows[("all-reduce", 4096)]
    assert sync["calls"] == 1
    assert sync["device_s"] == sync["exposed_s"] == pytest.approx(50 * us)
    asyn = rows[("all-reduce", 2048)]
    assert asyn["device_s"] == pytest.approx(80 * us)
    assert asyn["exposed_s"] == pytest.approx(20 * us)
    assert s["step_runs"] == 1


def test_clock_map_on_synthetic_pairs():
    """Ring spans (perf_counter s) against their annotations (profiler
    ns): the offset is the median difference; a name whose counts differ
    on the two sides is left out; the StepTraceAnnotation is no pair."""
    off = 7_000_000_000.0
    ring = [("step", 1.0, 1.5, 1, 1, 0, {}),
            ("step.execute", 1.1, 1.4, 1, 2, 1, {}),
            ("step", 2.0, 2.5, 1, 3, 0, {}),
            ("step.execute", 2.1, 2.4, 1, 4, 3, {}),
            ("data.fetch", 1.2, 1.3, 2, 5, 0, {}),
            ("data.fetch", 9.0, 9.1, 2, 6, 0, {})]   # after the window
    host = [["hvd_step", 1.0e9 + off + 100, 5e8, None],
            ["hvd_step.execute", 1.1e9 + off - 100, 3e8, None],
            ["hvd_step", 1.1e9 + off, 3e8, 7],      # StepTraceAnnotation
            ["hvd_step", 2.0e9 + off + 300, 5e8, None],
            ["hvd_step.execute", 2.1e9 + off, 3e8, None],
            ["hvd_step", 2.1e9 + off, 3e8, 8],
            ["hvd_data.fetch", 1.2e9 + off, 1e8, None]]
    cm = clock_map(ring, host, window=(0.5, 3.0))
    assert cm["pairs"] == 5
    assert cm["offset_ns"] == pytest.approx(off, abs=1.0)
    assert cm["spread_ns"] == pytest.approx(400.0, abs=1.0)
    # without the window the two data.fetch spans meet one annotation:
    # the name is left out, the others still pair
    assert clock_map(ring, host)["pairs"] == 4
    assert clock_map(ring, []) is None and clock_map([], host) is None
    # host self time and the skew bound ride the same spans
    s = summarize(
        {"lanes": {"l0": _lane([("dot.1", 0, 10)],
                               modules=[("jit_step", 8_100_200, 100),
                                        ("jit_step", 9_100_050, 100)])},
         "host": host, "files": []},
        build_op_table(SYNTH_HLO), ring, (0.5, 3.0))
    assert s["host"]["hvd_step"] == pytest.approx(2 * (0.5 - 0.3))
    assert s["host"]["hvd_step.execute"] == pytest.approx(0.6)
    assert s["clock"]["pairs"] == 5
    # device starts 200 us and 50 us after the mapped step.execute starts
    assert s["clock"]["host_device_skew_bound_us"] == pytest.approx(
        50.0, abs=0.01)
    # idle gaps: filed under the innermost span open at their middle,
    # unless shorter than the skew bound
    ops = [("dot.1", 8_100_200, 100),      # runs inside step.execute 1
           ("dot.1", 8_100_330, 100),      # 30 us later: under the bound
           ("dot.1", 8_300_000, 100),      # 199,570 us later, still inside
           ("dot.1", 8_700_000, 100)]      # the gap's middle: no span open
    s = summarize({"lanes": {"l0": _lane(ops, modules=[
        ("jit_step", 8_100_200, 100), ("jit_step", 9_100_050, 100)])},
        "host": host, "files": []},
        build_op_table(SYNTH_HLO), ring, (0.5, 3.0))
    by = s["idle"]["by"]
    assert by["under_skew_bound"] == pytest.approx(30e-6, rel=1e-3)
    assert by["hvd_step.execute"] == pytest.approx(199_570e-6, rel=1e-3)
    assert by["no_span"] == pytest.approx(399_900e-6, rel=1e-3)
    assert s["idle"]["idle_s"] == pytest.approx(sum(by.values()))
    # without a clock nothing can be given to a span
    s = summarize({"lanes": {"l0": _lane(ops)}, "host": [], "files": []})
    assert set(s["idle"]["by"]) == {"unmapped"}


def test_reader_on_recorded_v5e_trace_agrees_with_benchmark_reducer(
        tmp_path):
    """The xplane reader and the reduction on the v5e recording kept
    under benchmark/tests/data agree with the benchmark's own reducer
    (benchmark/lib/trace_reduce.py) per phase to rounding."""
    from benchmark.lib import trace_reduce as tr
    cap = tmp_path / "plugins" / "profile" / "t"
    cap.mkdir(parents=True)
    shutil.copy(os.path.join(RECORDED, "tiny_step.xplane.pb"), cap)
    with open(os.path.join(RECORDED, "tiny_step.hlo.txt"),
              encoding="utf-8") as f:
        hlo = f.read()
    events = read_capture(str(tmp_path))
    lane = events["lanes"]["tpu:0"]
    assert len(lane["modules"]) == 3 and lane["modules"][0][0] == "jit_step"
    # instruction names parsed from the events' HLO text
    assert any(op[0] == "convolution_tanh_fusion" for op in lane["ops"])
    s = summarize(events, build_op_table(hlo))
    red = tr.reduce_trace(tr.read_xplane(str(cap / "tiny_step.xplane.pb")),
                          tr.scope_map(hlo), drop_first=0)
    for phase, pattern in (
            ("forward", "^(?!.*hvd_backward).*hvd_forward"),
            ("backward", "hvd_backward"),
            ("optimizer", "^(?!.*hvd_exchange).*hvd_optimizer"),
            ("other", "^(?!.*hvd_)")):
        want = tr.select(red, "scope", pattern)["0"] * 1e-9
        assert s["phases"][phase] == pytest.approx(want, rel=1e-9)
    assert s["step_runs"] == 3 and s["lanes"] == 1
    # the recording's Pallas kernel predates the kernel names: it is
    # filed under its instruction's
    assert s["kernels"]["hvd_forward.1"]["calls"] == 3
    # the two fusions that hold a convolution are the matmuls, with the
    # FLOPs their operand types give; what rides along is read from the
    # fusions' bodies
    table = build_op_table(hlo)
    assert op_class(table["convolution_tanh_fusion"]) == "matmul"
    assert op_class(table["fusion"]) == "matmul"
    assert s["classes"]["forward"].keys() == {"matmul", "kernel"}
    assert s["classes"]["backward"].keys() == {"matmul"}
    rows = {r["scope"]: r for r in s["matmuls"]}
    assert rows["forward"]["flops"] == rows["backward"]["flops"] \
        == 2 * 8192 * 2048 * 2048
    assert rows["forward"]["rides"] == ["tanh"]
    assert rows["backward"]["rides"] == ["hvd_optimizer"]
    assert rows["backward"]["operands"] == "f32×f32"
    assert rows["backward"]["calls"] == 3
    # the capture names its chip: a v5e's peak, and what was lost to it
    assert events["device_kind"] == "TPU v5 Lite"
    assert rows["forward"]["at_peak_s"] == pytest.approx(
        2 * 8192 * 2048 * 2048 / 197e12)
    assert 0 < rows["forward"]["lost_s"] < rows["forward"]["device_s"]
    # scopes split the phases, classes split the scopes
    by_region = {}
    for path, sec in s["scopes"].items():
        region = path.split("/")[0]
        by_region[region] = by_region.get(region, 0.0) + sec
    for region, sec in by_region.items():
        assert sec == pytest.approx(s["phases"][region], rel=1e-12)
    assert sum(sum(by.values()) for by in s["classes"].values()) \
        == pytest.approx(s["total_s"], rel=1e-12)


def test_tick_owner_locking_and_window(monkeypatch, tmp_path):
    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: started.append(kw))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    tr = StepTracer(diag_dir=str(tmp_path))
    a, b = object(), object()
    drains = []
    tr.tick(owner=a, drain=lambda: drains.append("idle"))  # not armed
    assert not tr.active and tr.captures == 0 and not drains
    tr.arm(2)
    tr.tick(owner=a, drain=lambda: drains.append("start"))
    assert tr.active and drains == ["start"]
    # the python tracer is off in the capture's options
    assert started[0]["profiler_options"].python_tracer_level == 0
    tr.tick(owner=b)  # foreign ticker: owner lock ignores it
    assert tr._seen == 0
    tr.tick(owner=a, drain=lambda: drains.append("mid"))
    assert tr._seen == 1 and tr.active and drains == ["start"]
    tr.tick(owner=a, drain=lambda: drains.append("stop"))
    assert not tr.active and tr.captures == 1
    assert drains == ["start", "stop"]   # only at the capture's two ends
    # empty capture dir reduces to None, recorded as a summary-less window
    assert tr.last_summary is None
    meta = xla_trace.load_meta(tr.last_dir)
    assert meta["steps"] == 2 and meta["summary"] is None


def test_trace_steps_compiled_end_to_end(hvd_init, tmp_path):
    hvd = hvd_init
    mesh = hvd.mesh()

    def loss_fn(p, x, y):
        return jnp.mean((x @ p["w"] - y) ** 2)

    step = hvd.compiled_train_step(loss_fn, optax.sgd(0.01),
                                   name="xla_trace.e2e")
    params = jax.device_put({"w": jnp.ones((16, 4))},
                            NamedSharding(mesh, P()))
    opt_state = jax.device_put(step.init(params), NamedSharding(mesh, P()))
    x = jax.device_put(jnp.ones((16, 16)), NamedSharding(mesh, P("hvd")))
    y = jax.device_put(jnp.zeros((16, 4)), NamedSharding(mesh, P("hvd")))
    for _ in range(2):  # warmup/compile outside the capture
        params, opt_state, loss = step(params, opt_state, x, y)
    jax.block_until_ready(loss)

    tr = hvd.trace_steps(2, out_dir=str(tmp_path))
    assert tr.armed and xla_trace.get() is tr
    for _ in range(4):
        params, opt_state, loss = step(params, opt_state, x, y)
        jax.block_until_ready(loss)
    if tr.active or tr.armed:
        tr.stop()
    try:
        assert tr.captures == 1
        s = tr.last_summary
        assert s is not None, "no device events parsed from the capture"
        # the compiled step's regions are visible: compute in forward,
        # the in-graph psum exchange nonzero
        assert s["phases"]["forward"] > 0.0
        assert s["phases"]["exchange"] > 0.0
        # ... split by scope path and op class, and the matmuls among
        # them set against their FLOPs (a chip's shard: 2 of 16 rows)
        assert sum(s["scopes"].values()) == pytest.approx(s["total_s"])
        assert sum(v for p, v in s["scopes"].items()
                   if p.split("/")[0] == "forward") == pytest.approx(
                       s["phases"]["forward"])
        assert s["classes"].keys() == s["scopes"].keys()
        assert any("matmul" in by for by in s["classes"].values())
        dots = [r for r in s["matmuls"] if r["flops"] == 2 * 2 * 16 * 4]
        assert dots and all(r["calls"] >= 2 and r["device_s"] > 0.0
                            and r["operands"] == "f32×f32" for r in dots)
        assert all(r["flops"] is not None for r in s["matmuls"])
        meta = xla_trace.load_meta(tr.last_dir)
        assert meta["steps"] == 2 and meta["summary"] is not None
        assert meta["op_phases"]
        assert {"scopes", "classes", "matmuls"} <= meta["summary"].keys()
        # device-busy time per lane fits inside the capture wall window
        # (generous bound: CPU trace timestamps are coarse)
        assert s["total_s"] / s["lanes"] <= meta["wall_elapsed_s"] * 1.5
        # the gradient all-reduce is in the collectives, by message size
        assert any(r["op"] == "all-reduce" and r["bytes"] > 0
                   for r in s["collectives"])
        # the program's own spans: in the window's host self time, and
        # paired with their annotations for the clock
        assert s["host"]["hvd_step.execute"] > 0.0
        assert s["clock"]["pairs"] >= 4
        assert meta["mono_start"] > 0.0
        snap = hvd.metrics_snapshot()
        caps = snap["hvd_xla_trace_captures_total"]["values"].get("", 0.0)
        assert caps >= 1.0
        phases = snap["hvd_xla_phase_seconds"]["values"]
        assert phases['phase="exchange"'] > 0.0
        flops = snap["hvd_step_flops_total"]["values"].get("", 0.0)
        assert flops > 0.0 and step.flops_per_step > 0.0
        # the collectives reach the per-collective profile (profiler.txt)
        # under a label of their own, with device time
        stats = hvd.state().stats
        assert stats.counter("allreduce_xla") >= 1
        assert stats.total_time_us("allreduce_xla") >= 0
    finally:
        xla_trace.uninstall()


def test_disabled_by_default_builds_no_state(hvd_init):
    from horovod_tpu.diag import sentry
    # neither knob is on: no tracer, no sentry, nothing on disk
    assert xla_trace.get() is None
    assert sentry.get() is None
    diag_dir = os.environ["HOROVOD_DIAG_DIR"]
    entries = os.listdir(diag_dir) if os.path.isdir(diag_dir) else []
    assert not [d for d in entries if d.startswith("xla-trace")]
    assert not [d for d in entries if d.startswith("perf-baseline")]


def test_env_knob_installs_armed_tracer(monkeypatch, tmp_path):
    monkeypatch.setenv("HOROVOD_XPROF_STEPS", "3")
    from horovod_tpu.config import Config
    cfg = Config.from_env()
    assert cfg.xprof_steps == 3
    try:
        tr = xla_trace.install(cfg)
        assert tr is not None and tr.armed
        assert xla_trace.get() is tr
    finally:
        xla_trace.uninstall()
    monkeypatch.setenv("HOROVOD_XPROF_STEPS", "0")
    assert xla_trace.install(Config.from_env()) is None
    assert xla_trace.get() is None


def _recorded_capture(tdir):
    cap = tdir / "plugins" / "profile" / "t"
    cap.mkdir(parents=True)
    shutil.copy(os.path.join(RECORDED, "tiny_step.xplane.pb"), cap)
    with open(os.path.join(RECORDED, "tiny_step.hlo.txt"),
              encoding="utf-8") as f:
        return build_op_table(f.read())


def test_cli_xla_trace_merge(tmp_path, capsys):
    """The merger lays the device events of a capture on the flight
    dumps' wall clock through the sidecar's clock mapping: profiler ns
    -> perf_counter (summary.clock.offset_ns) -> wall (mono_start /
    wall_start)."""
    from horovod_tpu.diag.__main__ import main
    tdir = tmp_path / "xla-trace-001"
    table = _recorded_capture(tdir)
    summary = summarize(read_capture(str(tdir)), table)
    # the recording's first device op starts at 46,179,537 ns on the
    # profiler's clock; say that instant was perf_counter 50.0 s, and
    # that the capture started at perf_counter 49.9 s = wall 100.0 s
    summary["clock"] = {"offset_ns": 46_179_537.0 - 50.0e9, "pairs": 12,
                        "spread_ns": 900.0,
                        "host_device_skew_bound_us": 271.0}
    (tdir / xla_trace.META_FILENAME).write_text(json.dumps(
        {"version": 2, "rank": 0, "steps": 3, "wall_start": 100.0,
         "wall_stop": 101.0, "wall_elapsed_s": 1.0, "mono_start": 49.9,
         "summary": summary,
         "op_phases": {k: [phase_of_op_name(v[2]), None]
                       for k, v in table.items() if v[2]}}))
    (tmp_path / "flight-rank0.json").write_text(json.dumps(
        {"rank": 0, "events": [
            {"seq": 0, "t": 50.0, "wall": 100.2, "ev": "step", "dt": 0.1,
             "step": 1},
            {"seq": 1, "t": 50.0, "wall": 100.1, "ev": "span",
             "name": "step.execute", "t0": 49.95, "tid": 1, "id": 2,
             "parent": 1}]}))
    merged = tmp_path / "merged.json"
    rep_path = tmp_path / "report.json"
    rc = main([str(tmp_path), "--xla-trace", str(tdir),
               "--trace", str(merged), "--json", str(rep_path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "forward=" in out and "exchange=" in out and "optimizer=" in out
    assert "kernels ms/step/lane" in out and "12 span pairs" in out
    rep = json.loads(rep_path.read_text())
    assert rep["xla"]["phases"]["forward"] > 0.0
    assert rep["xla"]["aligned"] is True
    doc = json.loads(merged.read_text())
    evs = doc["traceEvents"] if isinstance(doc, dict) else doc
    xla_evs = [e for e in evs if e.get("cat") in
               ("forward", "backward", "optimizer", "other")]
    assert len(xla_evs) == 45
    # the device events landed phase-labeled, joined via the sidecar map
    assert {"forward", "backward", "optimizer"} <= {e["cat"]
                                                    for e in xla_evs}
    # t=0 of the merged trace is the earliest start: the span's, at wall
    # 100.05 s; the first device op ran at wall 100.1 s, 50 ms later
    assert min(e["ts"] for e in xla_evs) == pytest.approx(50_000, abs=2)
    span = [e for e in evs if e.get("cat") == "span"]
    assert len(span) == 1 and span[0]["dur"] == pytest.approx(50_000, abs=2)


def test_cli_xla_trace_without_flight_dumps(tmp_path, capsys):
    """No sidecar: the capture is re-reduced (everything 'other' without
    the HLO) and reported as not clock-aligned."""
    from horovod_tpu.diag.__main__ import main
    tdir = tmp_path / "xla-trace-001"
    _recorded_capture(tdir)
    rc = main([str(tmp_path), "--xla-trace", str(tdir)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "xla device trace" in out and "not clock-aligned" in out


@pytest.mark.parametrize("as_file", [False, True])
def test_cli_xla_trace_reduces_a_dump_beside_its_hlo(tmp_path, capsys,
                                                     as_file):
    """No sidecar, but the HLO text of the program that ran lies beside
    the capture (the benchmark's ``--dump-dir``): the capture is joined
    against it, and the report has the scope x class table and the matmul
    rows. The capture may be named as its directory or as the file."""
    from horovod_tpu.diag.__main__ import main
    for ext in ("xplane.pb", "hlo.txt"):
        shutil.copy(os.path.join(RECORDED, f"tiny_step.{ext}"), tmp_path)
    target = tmp_path / "tiny_step.xplane.pb" if as_file else tmp_path
    rep_path = tmp_path / "report.json"
    assert main([str(tmp_path), "--xla-trace", str(target),
                 "--json", str(rep_path)]) == 0
    out = capsys.readouterr().out
    assert "steps=3 lanes=1" in out and "forward=0.569" in out
    assert "by scope path and op class" in out
    rows = {ln.split()[0]: ln.split() for ln in out.splitlines()
            if ln.startswith("  ") and len(ln.split()) == 7}
    assert rows["scope"][1:] == ["total", "matmul", "kernel", "reduce",
                                 "copy", "other"]
    assert rows["forward"][1:4] == ["0.569", "0.362", "0.207"]
    assert "matmul fusions: 2 rows, 0.1374 TFLOP/step/lane" in out
    assert "f32×f32  backward  f32[8192,2048] * f32[8192,2048] -> " \
        "f32[2048,2048]  rides: hvd_optimizer" in out
    assert json.loads(rep_path.read_text())["xla"]["phases"]["forward"] \
        == pytest.approx(0.001707939)
