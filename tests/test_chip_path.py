"""What keeps a CPU from passing for the chip (fast, CPU-only).

The chip itself is reached only through ``python chip_smoke.py`` on a
machine that has one. These tests pin the properties that make that run
mean something: a launcher/bench parent that never opens a backend (a
chip belongs to one process), a compile cache that can be placed from
outside, an MFU denominator that is never guessed, and a smoke script
that refuses a CPU.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_parent_imports_open_no_backend():
    """The launcher, the serve package and the bench module are imported
    by parents whose children need the chip; importing them must leave
    jax without an initialised backend."""
    out = subprocess.run(
        [sys.executable, "-c",
         "import horovod_tpu.run.run, horovod_tpu.serve, bench_transformer\n"
         "from jax._src import xla_bridge\n"
         "assert not xla_bridge.backends_are_initialized(), 'backend open'\n"
         "print('clean')"],
        cwd=REPO, env=dict(os.environ), capture_output=True, text=True,
        timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("clean")


def test_compile_cache_placed_from_outside(monkeypatch):
    import jax

    from horovod_tpu import runtime

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert runtime.compile_cache_dir() == "/somewhere/else"
    runtime._place_compile_cache()
    assert updates == []  # jax reads the variable itself; nothing set in code

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert runtime.compile_cache_dir() == fixed
    runtime._place_compile_cache()
    assert updates == [("jax_compilation_cache_dir", fixed)]


def test_peak_flops_is_never_guessed():
    from horovod_tpu import hardware
    assert hardware.peak_flops_for_kind("TPU v5 lite") == 197e12
    assert hardware.peak_flops_for_kind("cpu") == 0.0  # "no MFU", no raise
    for kind in ("", "TPU", "TPU v5", "TPU v5 lite pod", "TPU v99"):
        with pytest.raises(ValueError, match="no peak FLOPs known"):
            hardware.peak_flops_for_kind(kind)


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         cwd=REPO, env=env, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "REFUSED" in out.stderr and "'cpu', not 'tpu'" in out.stderr
    assert '"ok"' not in out.stdout  # no result line off the chip
