"""Host spans inside the program (diag/recorder.py ``span``; docs/
diagnostics.md "Host spans"): nesting, parent ids and thread separation
in the flight ring; the ``step`` / ``data.*`` / ``bcast.*`` / ``jax.*``
spans a tiny compiled loop leaves behind, with the right parents; what a
span does when no capture is active — asserted by counting ring stores
and annotation calls, never by a wall-clock bound; the kernels' and the
head's device names in the step's jaxpr; and the TelemetryCallback's
step time under an asynchronous dispatch."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import diag
from horovod_tpu.diag import recorder


@pytest.fixture
def ring(hvd_init):
    """The live flight ring. An earlier test file of the same worker may
    have left the runtime up with the recorder uninstalled or disabled
    (test_flight_recorder.py toggles it): put one back."""
    rec = diag.get()
    if rec is None:
        from horovod_tpu.config import Config
        rec = recorder.install(Config.from_env())
    assert rec is not None
    return rec


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s[0], []).append(s)
    return out


def test_span_nesting_parent_ids_and_thread_separation(ring):
    seen = {}

    def worker():
        with diag.span("t.outer", who="worker") as o:
            with diag.span("t.inner") as i:
                seen["worker"] = (o.id, i.id, i.parent)

    with diag.span("t.outer", who="main") as outer:
        t = threading.Thread(target=worker)
        t.start()
        t.join()
        with diag.span("t.inner", k=1) as inner:
            inner.set(found=7)
        with diag.span("t.inner", k=2) as inner2:
            pass
    mine = [s for s in diag.spans() if s[0].startswith("t.")]
    assert len(mine) == 5
    by_id = {s[4]: s for s in mine}
    # the worker's spans nest among themselves, not under main's open span
    w_outer, w_inner, w_parent = seen["worker"]
    assert w_parent == w_outer and by_id[w_outer][5] == 0
    assert by_id[w_outer][3] != by_id[outer.id][3]          # thread ids
    assert by_id[w_inner][3] == by_id[w_outer][3]
    # main's two inner spans are siblings under the outer one
    assert by_id[inner.id][5] == outer.id == by_id[inner2.id][5]
    assert by_id[outer.id][5] == 0
    assert by_id[inner.id][6] == {"k": 1, "found": 7}
    assert by_id[outer.id][6] == {"who": "main"}
    # a child lies inside its parent on the one clock
    for child, parent in ((inner, outer), (inner2, outer)):
        c, p = by_id[child.id], by_id[parent.id]
        assert p[1] <= c[1] <= c[2] <= p[2]
    # ids are unique and spans() is ordered by start
    assert len({s[4] for s in mine}) == 5
    assert [s[1] for s in mine] == sorted(s[1] for s in mine)


def test_span_without_capture_is_one_ring_store(ring, monkeypatch):
    """No profiler session: a span is exactly one ring entry, one
    annotation entered and left once, and nothing else."""
    calls = []

    class Note:
        def __init__(self, name, **kw):
            calls.append(("init", name, kw))

        def __enter__(self):
            calls.append(("enter",))

        def __exit__(self, *exc):
            calls.append(("exit",))

    monkeypatch.setattr(recorder, "TraceAnnotation", Note)
    monkeypatch.setattr(recorder, "StepTraceAnnotation", Note)
    rec = ring
    before = rec.events_recorded
    with diag.span("count.me", step=3):
        pass
    assert rec.events_recorded == before + 1
    assert calls == [("init", "hvd_count.me", {}), ("enter",), ("exit",)]
    del calls[:]
    with diag.span("step.execute", step_trace=9):
        pass
    # the step annotation encloses the span's own, both once
    assert rec.events_recorded == before + 2
    assert calls == [("init", "hvd_step.execute", {}),
                     ("init", "hvd_step", {"step_num": 9}),
                     ("enter",), ("enter",), ("exit",), ("exit",)]
    entry = rec.snapshot()[-1]
    assert entry["ev"] == "span" and entry["name"] == "step.execute"
    assert {"t0", "tid", "id", "parent"} <= set(entry)
    # the existing event API and phase_totals are untouched by spans
    totals = rec.phase_totals()
    assert totals["steps"] == 0 and totals["input_s"] == 0.0
    # an exception leaves the thread's span stack clean
    with pytest.raises(ValueError):
        with diag.span("boom"):
            raise ValueError("x")
    with diag.span("after") as after:
        pass
    assert after.parent == 0


def test_spans_before_init_are_adopted_and_survive_shutdown():
    import horovod_tpu as hvd
    hvd.shutdown()
    diag.record_span("early.one", 1.0, 2.0, why="before init")
    assert any(s[0] == "early.one" for s in diag.spans())
    hvd.init()
    names = [s[0] for s in diag.spans()]
    assert "early.one" in names and "init" in names
    assert not recorder._early            # adopted, not kept twice
    with diag.span("late.one"):
        pass
    hvd.shutdown()
    # a harness reads its run's spans after hvd.shutdown()
    names = [s[0] for s in diag.spans()]
    assert "late.one" in names and "early.one" in names
    hvd.init()


def test_import_span_is_recorded_once():
    import horovod_tpu  # noqa: F401  (already imported: the span is there)
    # the ring of a long test session may have lapped it; the module
    # records it exactly once, at the last line of the package
    src = open(horovod_tpu.__file__, encoding="utf-8").read()
    assert src.count('diag.record_span("import"') == 1
    assert src.rstrip().splitlines()[-1].startswith(
        'diag.record_span("import"')


def test_compiled_loop_leaves_step_data_bcast_spans(ring, monkeypatch):
    import horovod_tpu as hvd
    from horovod_tpu import runtime
    # keep every jax.* span, however short a toy program's compile is
    monkeypatch.setattr(runtime, "_JAX_SPAN_MIN_S", 0.0)
    mesh = hvd.mesh()
    rep = NamedSharding(mesh, P())

    def loss_fn(p, x, y):
        return jnp.mean((x @ p["w"] - y) ** 2)

    params = {"w": jnp.ones((16, 4)), "b": jnp.zeros((4,))}
    t_start = recorder.perf_counter()
    params = jax.device_put(hvd.broadcast_parameters(params), rep)
    step = hvd.compiled_train_step(loss_fn, optax.sgd(0.01),
                                   name="spans.loop")
    opt_state = jax.device_put(step.init(params), rep)

    def source(idx):
        idx = np.asarray(list(idx))
        return (np.ones((len(idx), 16), np.float32),
                np.zeros((len(idx), 4), np.float32))

    ds = hvd.data.DistributedDataset(
        source, batch_size=16, num_samples=16 * 64, shuffle=False,
        sharding=NamedSharding(mesh, P("hvd")))
    it = iter(ds)
    for _ in range(4):
        batch = next(it)
        params, opt_state, loss = step(params, opt_state, *batch)
    jax.block_until_ready(loss)
    ds.close()
    spans = [s for s in diag.spans() if s[1] >= t_start]
    names = _by_name(spans)
    by_id = {s[4]: s for s in spans}

    # the broadcast and its two parts
    (bcast,) = names["bcast"]
    assert bcast[6]["leaves"] == 2 and bcast[6]["bytes"] == (64 + 4) * 4
    assert names["bcast.host_pull"][0][5] == bcast[4]
    assert names["bcast.engine"][0][5] == bcast[4]

    # one `step` per call, numbered, the first a cache miss; its parts
    # are its children
    steps = names["step"]
    assert [s[6]["step"] for s in steps] == [1, 2, 3, 4]
    assert [s[6]["hit"] for s in steps] == [False, True, True, True]
    for part in ("step.signature", "step.lookup", "step.execute"):
        assert len(names[part]) == 4
        assert [s[5] for s in names[part]] == [s[4] for s in steps]
    (analyze,) = names["step.analyze"]          # once per signature
    assert analyze[5] == steps[0][4]
    (read_hlo,) = names["step.read_hlo"]        # and after its first run
    assert read_hlo[5] == steps[0][4]
    assert read_hlo[1] >= names["step.execute"][0][2]
    # jax's own durations hang under whichever span was open: the trace
    # and the lowering under step.analyze (it lowers first), the backend
    # compile or cache load under the first step.execute
    under = {}
    for s in spans:
        if s[0].startswith("jax.") and s[5] in by_id:
            under.setdefault(by_id[s[5]][0], set()).add(s[0])
    assert under.get("step.execute", set()) & {"jax.compile",
                                               "jax.cache_load"}
    assert "jax.lower" in under.get("step.analyze", set())

    # the loader: the consumer's wait on the loop's thread, fetch and put
    # on the producer's
    waits = names["data.wait"]
    assert len(waits) == 4 and {s[3] for s in waits} == {steps[0][3]}
    assert all("depth" in s[6] for s in waits)
    fetch, put = names["data.fetch"], names["data.put"]
    assert len(fetch) >= 4 and len(put) >= 4
    assert {s[3] for s in fetch} == {s[3] for s in put} != {steps[0][3]}
    assert [s[6]["batch"] for s in fetch[:4]] == [0, 1, 2, 3]
    # hvd_data_input_wait_seconds is fed as before
    snap = hvd.metrics_snapshot()
    assert snap["hvd_data_input_wait_seconds"]["values"][""]["count"] >= 4


def test_kernel_and_head_names_in_the_step_jaxpr():
    """The Pallas kernels run under names of their own and the head +
    cross entropy under ``hvd_head_ce``; no new name contains a
    step-region label, so the readers that search ``hvd_forward`` ...
    anywhere in the path file every op where they did."""
    import re

    from horovod_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(
        vocab_size=256, d_model=128, n_heads=2, n_layers=1, d_ff=256,
        max_seq=128, attention_impl="flash", flash_interpret=True,
        loss_chunk=64, remat=True, dtype=jnp.bfloat16)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((2, 128), jnp.int32)

    def step(p, a, b):
        with jax.named_scope("hvd_forward"):
            loss, bwd = jax.vjp(lambda q: tfm.loss_fn(q, a, b, cfg), p)
        with jax.named_scope("hvd_backward"):
            (g,) = bwd(jnp.ones_like(loss))
        return loss, g

    jaxpr = str(jax.make_jaxpr(step)(params, tok, tok))
    assert set(re.findall(r"name=(hvd_\w+)", jaxpr)) == {
        "hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"}
    text = jax.jit(step).lower(params, tok, tok).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*hvd_[^"]*)"', text))
    # forward kernel under forward; its recomputed call, dQ and dK/dV
    # under backward first
    assert any(re.match(r"[^/]*/hvd_forward/.*hvd_flash_fwd", p)
               for p in paths)
    # (each kernel inside its layer's attention scope, hvd_attn_full here)
    assert any(re.match(r"[^/]*/hvd_backward/.*rematted_computation/"
                        r"hvd_attn_full/hvd_flash_fwd", p) for p in paths)
    for name in ("hvd_flash_dq", "hvd_flash_dkv"):
        assert any(re.match(rf"[^/]*/hvd_backward/.*{name}", p)
                   for p in paths)
    assert any("hvd_head_ce" in p and "hvd_forward" in p for p in paths)
    assert any("hvd_head_ce" in p and "hvd_backward" in p for p in paths)
    from horovod_tpu.diag.xla_trace import phase_of_op_name
    for name in ("hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv",
                 "hvd_flash_band_fwd", "hvd_flash_band_dq",
                 "hvd_flash_band_dkv", "hvd_head_ce", "hvd_attn_full",
                 "hvd_attn_window", "hvd_gmm", "hvd_moe", "hvd_moe_route",
                 "hvd_moe_dispatch", "hvd_moe_experts", "hvd_moe_combine",
                 "hvd_moe_shared", "hvd_ssm", "hvd_ssm_in_proj",
                 "hvd_ssm_conv", "hvd_ssm_scan", "hvd_ssm_norm",
                 "hvd_ssm_out_proj", "hvd_kda", "hvd_kda_in_proj",
                 "hvd_kda_conv", "hvd_kda_scan", "hvd_kda_norm",
                 "hvd_kda_out_proj", "hvd_mla_proj", "hvd_sconv",
                 "hvd_sconv_in_proj", "hvd_sconv_gate",
                 "hvd_sconv_out_proj", "hvd_qk_norm"):
        assert phase_of_op_name(f"jit(f)/{name}/x") is None


@pytest.mark.parametrize("compiled", [False, True])
def test_telemetry_step_time_is_between_step_ends(monkeypatch, compiled):
    """With ``compiled_step=`` the step's time is the interval between
    successive step ends (a compiled step returns at its enqueue);
    without, begin to end as before. A fake clock, no sleeping."""
    from horovod_tpu import callbacks, metrics
    now = [100.0]
    monkeypatch.setattr(callbacks.time, "perf_counter", lambda: now[0])
    seen = []
    monkeypatch.setattr(metrics.STEP_SECONDS, "observe", seen.append)

    class Step:
        flops_per_step = 0.0
        perf_signature = "x"

    cb = callbacks.TelemetryCallback(batch_size=8, skew_interval=0,
                                     policy_dir="",
                                     compiled_step=Step() if compiled
                                     else None)
    cb.on_train_begin()
    for i in range(3):
        now[0] += 0.5          # input, logging ... between steps
        cb.on_batch_begin(i)
        now[0] += 0.01         # the enqueue
        cb.on_batch_end(i)
    if compiled:
        # the first step has no previous end: begin -> end
        assert seen == pytest.approx([0.01, 0.51, 0.51])
    else:
        assert seen == pytest.approx([0.01, 0.01, 0.01])
    # a pause between epochs is not a step
    now[0] += 60.0
    cb.on_epoch_begin(1)
    cb.on_batch_begin(3)
    now[0] += 0.01
    cb.on_batch_end(3)
    assert seen[-1] == pytest.approx(0.01)
