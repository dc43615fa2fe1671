"""Launcher integration: real multi-process jobs over the jax.distributed
coordination service (no MPI).

Reference analog: the reference tests everything under ``mpirun -np N``
(.buildkite/gen-pipeline.sh:100); here ``horovodrun -np N`` itself is under
test, spawning genuine separate processes that wire up through the
coordinator and run a cross-process XLA collective.
"""

import os
import subprocess
import sys
import textwrap

import pytest

from horovod_tpu.run import parse_args
from horovod_tpu.run.run import _parse_hosts, launch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_parse_args_requires_np():
    with pytest.raises(SystemExit):
        parse_args(["python", "x.py"])


def test_parse_args_full():
    args = parse_args(["-np", "4", "-H", "a:2,b:2", "--start-timeout", "10",
                       "python", "train.py"])
    assert args.np == 4
    assert args.host == "a:2,b:2"
    assert args.command == ["python", "train.py"]


def test_parse_hosts():
    assert _parse_hosts(None, 4) == [("localhost", 4)]
    assert _parse_hosts("h1:2,h2:3", 5) == [("h1", 2), ("h2", 3)]
    with pytest.raises(ValueError, match="slots"):
        _parse_hosts("h1:1", 4)


def _write_child(tmp_path, body):
    script = tmp_path / "child.py"
    preamble = textwrap.dedent(f"""\
        import sys
        sys.path.insert(0, {REPO!r})
        import jax
        jax.config.update("jax_platforms", "cpu")
        """)
    script.write_text(preamble + textwrap.dedent(body))
    return str(script)


def test_launch_two_process_collective(tmp_path):
    """Two real processes join through the coordinator and psum across
    process boundaries — the reference's 'mpirun -np 2' equivalent."""
    child = _write_child(tmp_path, textwrap.dedent("""\
        import horovod_tpu as hvd
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        hvd.init()
        assert hvd.size() == 2, hvd.size()
        assert jax.process_count() == 2
        mesh = hvd.mesh()
        pid = jax.process_index()

        # cross-process psum on the jit path
        x = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("hvd")),
            jnp.full((1, 4), float(pid + 1)))
        total = jax.jit(
            jax.shard_map(lambda v: jax.lax.psum(v, "hvd"), mesh=mesh,
                          in_specs=P("hvd"), out_specs=P("hvd")))(x)
        import numpy as np
        local = np.asarray(total.addressable_shards[0].data)
        np.testing.assert_allclose(local[0], np.full(4, 3.0))
        print(f"RANK{hvd.rank()}OK")
        """))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # 1 CPU device per process -> 2 ranks total
    rc = launch(2, [sys.executable, child], start_timeout=60, env=env)
    assert rc == 0


def test_launch_propagates_failure(tmp_path):
    child = _write_child(tmp_path, "import sys; sys.exit(3)")
    env = dict(os.environ)
    rc = launch(2, [sys.executable, child], start_timeout=60, env=env)
    assert rc != 0


def test_cache_roundtrip_and_staleness(tmp_path):
    from horovod_tpu.run.cache import Cache, parameters_hash
    h = parameters_hash("h1:2,h2:2", None)
    c = Cache(cache_folder=str(tmp_path), params_hash=h)
    assert c.get(("ssh", "h1", None)) is None
    c.put(("ssh", "h1", None), True)
    assert c.get(("ssh", "h1", None)) is True
    # survives reload with the same parameters...
    c2 = Cache(cache_folder=str(tmp_path), params_hash=h)
    assert c2.get(("ssh", "h1", None)) is True
    # ...is invalidated when the launch parameters change...
    c3 = Cache(cache_folder=str(tmp_path),
               params_hash=parameters_hash("other:4", 22))
    assert c3.get(("ssh", "h1", None)) is None
    # ...and entries go stale
    c4 = Cache(cache_folder=str(tmp_path), params_hash=h,
               staleness_minutes=0)
    c4.put(("ssh", "h2", None), True)
    import time
    time.sleep(0.01)
    assert c4.get(("ssh", "h2", None)) is None


def test_ssh_check_uses_cache(tmp_path):
    from horovod_tpu.run.cache import Cache
    from horovod_tpu.run.run import check_all_hosts_ssh_successful
    calls = []

    def fake_ssh(host):
        calls.append(host)
        return (0, "") if host != "bad" else (1, "boom")

    cache = Cache(cache_folder=str(tmp_path), params_hash="x")
    assert check_all_hosts_ssh_successful(["remote1", "remote2"],
                                          fn_cache=cache, _ssh_exec=fake_ssh)
    assert sorted(calls) == ["remote1", "remote2"]
    # second run: cache hits, no probes
    calls.clear()
    assert check_all_hosts_ssh_successful(["remote1", "remote2"],
                                          fn_cache=cache, _ssh_exec=fake_ssh)
    assert calls == []
    # localhost is never probed; a failing host raises with the message
    import pytest
    with pytest.raises(RuntimeError, match="SSH was not successful"):
        check_all_hosts_ssh_successful(["localhost", "bad"],
                                       fn_cache=None, _ssh_exec=fake_ssh)


def test_parse_args_max_restarts():
    args = parse_args(["-np", "2", "--max-restarts", "3", "cmd"])
    assert args.max_restarts == 3
    # unset resolves lazily in main() (env HOROVOD_MAX_RESTARTS or 0)
    assert parse_args(["-np", "2", "cmd"]).max_restarts is None


def test_main_gang_restart_recovers(tmp_path, capfd):
    """A job that fails on its first gang attempt succeeds after the
    launcher's whole-job restart (--max-restarts): the TPU-idiomatic
    elastic recovery — gang restart + resume from checkpoint (no partial
    worlds; beyond the reference, which always fails fast)."""
    from horovod_tpu.run.run import main

    marker = tmp_path / "attempted"
    child = _write_child(tmp_path, textwrap.dedent(f"""\
        import os, sys
        marker = {str(marker)!r}
        first = not os.path.exists(marker)
        if first:
            open(marker, "w").write("x")
            sys.exit(3)   # simulated rank failure on the first attempt
        print("RECOVERED")
        """))
    env_keep = dict(os.environ)
    try:
        os.environ["JAX_PLATFORMS"] = "cpu"
        rc = main(["-np", "2", "--max-restarts", "1",
                   sys.executable, child])
    finally:
        os.environ.clear()
        os.environ.update(env_keep)
    assert rc == 0
    err = capfd.readouterr().err
    assert "restarting (attempt 2/2)" in err


def test_main_gang_restart_exhausted(tmp_path, capfd):
    from horovod_tpu.run.run import main

    child = _write_child(tmp_path, "import sys; sys.exit(5)")
    env_keep = dict(os.environ)
    try:
        os.environ["JAX_PLATFORMS"] = "cpu"
        rc = main(["-np", "1", "--max-restarts", "1",
                   sys.executable, child])
    finally:
        os.environ.clear()
        os.environ.update(env_keep)
    assert rc == 5
    assert "attempt 2/2" in capfd.readouterr().err


def test_job_code_signal_killed_rank_is_failure():
    """A rank killed by a signal (negative code) fails the job even when
    another rank exited 0 — max() alone would call it clean."""
    from horovod_tpu.run.run import _job_code
    assert _job_code([0, -9]) == 1
    assert _job_code([0, 0]) == 0
    assert _job_code([0, 3, -9]) == 3
    assert _job_code([]) == 1


def test_main_config_error_fails_fast(capfd):
    """Static config errors (slots < np) never enter the restart loop."""
    from horovod_tpu.run.run import main
    rc = main(["-np", "4", "-H", "localhost:1", "--max-restarts", "5",
               "true"])
    assert rc == 1
    err = capfd.readouterr().err
    assert "Host slots" in err
    assert "restarting" not in err


def test_main_malformed_env_max_restarts(capfd, monkeypatch):
    from horovod_tpu.run.run import main
    monkeypatch.setenv("HOROVOD_MAX_RESTARTS", "banana")
    rc = main(["-np", "4", "-H", "localhost:1", "true"])
    assert rc == 1  # reaches the config error, not an int() traceback
    assert "ignoring malformed" in capfd.readouterr().err


def test_python_dash_m_entry():
    """python -m horovod_tpu.run == horovodrun (reference exposes the CLI
    as both a console script and bin/horovodrun)."""
    out = subprocess.run([sys.executable, "-m", "horovod_tpu.run",
                          "--version"], capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0
    assert out.stdout.strip()


def test_tpu_slots_one_chip_per_process(monkeypatch):
    """A chip belongs to one process: on a TPU host local slots each get a
    chip of their own, or the launch is refused — never N workers left to
    race for the same chips. (The chip count is faked; the pinned grid
    itself ran on a 4-chip v5e host — docs/launcher.md.)"""
    from horovod_tpu.run import run as launcher
    local = [("localhost", 4)]
    monkeypatch.setattr(launcher, "_local_tpu_chips", lambda: 4)
    slots = launcher._tpu_slot_envs({}, local, 4)
    assert [s["TPU_VISIBLE_CHIPS"] for s in slots] == ["0", "1", "2", "3"]
    assert len({s["TPU_PROCESS_PORT"] for s in slots}) == 4
    assert len({s["TPU_PROCESS_ADDRESSES"] for s in slots}) == 1
    assert all(s["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               and s["TPU_PROCESS_BOUNDS"] == "2,2,1" for s in slots)
    env = launcher._rank_env({}, "localhost:1", 4, 2, 2, 4, 0, 1, slots)
    assert env["TPU_VISIBLE_CHIPS"] == "2"
    # one process drives every chip itself; a CPU job opens none
    assert launcher._tpu_slot_envs({}, [("localhost", 1)], 1) is None
    assert launcher._tpu_slot_envs({"JAX_PLATFORMS": "cpu"}, local, 4) is None
    # any other local shape would race: refused, naming the way out
    with pytest.raises(ValueError, match="Run ONE process"):
        launcher._tpu_slot_envs({}, [("localhost", 2)], 2)
    with pytest.raises(ValueError, match="--elastic"):
        launch(4, ["true"], elastic=True, env={})
    monkeypatch.setattr(launcher, "_local_tpu_chips", lambda: 0)
    assert launcher._tpu_slot_envs({}, local, 4) is None
