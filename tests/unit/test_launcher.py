"""Launcher: hostfile parsing, include/exclude filtering, world-info
round-trip, rank resolution, and a REAL 2-process CPU smoke launch through
the CLI (reference strategy: "multi-node" exercised as multi-process on one
host, SURVEY §4 / ``tests/unit/test_run.py``)."""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from deepspeed_tpu.launcher.launch import resolve_node_rank
from deepspeed_tpu.launcher.runner import (decode_world_info,
                                           encode_world_info, fetch_hostfile,
                                           filter_resources)

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "..")


def test_fetch_hostfile(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("""
# comment
worker-0 slots=4
worker-1 slots=2  # trailing comment
""")
    assert fetch_hostfile(str(hf)) == {"worker-0": 4, "worker-1": 2}
    assert fetch_hostfile(str(tmp_path / "missing")) == {}


def test_fetch_hostfile_rejects_malformed(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("worker-0 gpus=4\n")
    with pytest.raises(ValueError):
        fetch_hostfile(str(hf))
    hf.write_text("worker-0 slots=4\nworker-0 slots=2\n")
    with pytest.raises(ValueError):
        fetch_hostfile(str(hf))


def test_filter_include():
    pool = {"w0": 4, "w1": 4, "w2": 2}
    assert filter_resources(pool, include="w0@w1:0,2") == {
        "w0": [0, 1, 2, 3], "w1": [0, 2]}
    with pytest.raises(AssertionError):
        filter_resources(pool, include="w9")
    with pytest.raises(AssertionError):
        filter_resources(pool, include="w2:5")


def test_filter_exclude():
    pool = {"w0": 4, "w1": 4}
    assert filter_resources(pool, exclude="w1") == {"w0": [0, 1, 2, 3]}
    assert filter_resources(pool, exclude="w0:1,3") == {
        "w0": [0, 2], "w1": [0, 1, 2, 3]}
    with pytest.raises(AssertionError):
        filter_resources(pool, include="w0", exclude="w1")


def test_world_info_roundtrip():
    active = {"a": [0, 1], "b": [0]}
    assert decode_world_info(encode_world_info(active)) == active


def test_resolve_node_rank():
    world = {"nodeA": [0], "nodeB": [0]}
    assert resolve_node_rank("1", world) == 1
    host = socket.gethostname()
    world2 = {"other": [0], host: [0]}
    assert resolve_node_rank("auto", world2) == 1
    with pytest.raises(RuntimeError):
        resolve_node_rank("auto", {"nope": [0]})


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_dataloader_process_slicing():
    """Each process sees its contiguous slice of every global batch, in a
    deterministic shared order (multi-host data contract)."""
    from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader

    data = [np.full((2,), i, np.float32) for i in range(16)]
    full = list(DeepSpeedDataLoader(data, batch_size=8, shuffle=True, seed=7))
    r0 = list(DeepSpeedDataLoader(data, batch_size=8, shuffle=True, seed=7,
                                  data_parallel_world_size=2,
                                  data_parallel_rank=0))
    r1 = list(DeepSpeedDataLoader(data, batch_size=8, shuffle=True, seed=7,
                                  data_parallel_world_size=2,
                                  data_parallel_rank=1))
    assert len(full) == len(r0) == len(r1) == 2
    for fb, a, b in zip(full, r0, r1):
        np.testing.assert_array_equal(np.concatenate([a, b]), fb)


@pytest.mark.slow
def test_two_process_cli_launch(tmp_path):
    """End-to-end: CLI -> spawner -> 2 processes -> jax.distributed
    rendezvous -> sliced dataloader -> 3 engine steps on a global mesh."""
    hostfile = tmp_path / "hostfile"
    hostfile.write_text(f"{socket.gethostname()} slots=2\n")
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "launcher_smoke_script.py")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.pop("JAX_PLATFORMS", None)
    cmd = [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
           "--hostfile", str(hostfile),
           "--master_addr", "127.0.0.1",
           "--master_port", str(_free_port()),
           script, str(tmp_path)]
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True, timeout=280)
    assert proc.returncode == 0, (
        f"launcher failed\nstdout:\n{proc.stdout[-3000:]}\n"
        f"stderr:\n{proc.stderr[-3000:]}")
    for rank in (0, 1):
        ok = tmp_path / f"rank{rank}.ok"
        assert ok.exists(), f"rank {rank} did not finish"
    l0 = (tmp_path / "rank0.ok").read_text()
    l1 = (tmp_path / "rank1.ok").read_text()
    assert l0 == l1, f"ranks diverged: {l0} vs {l1}"


def _mpi_args(hostfile, launcher, include=""):
    from deepspeed_tpu.launcher.runner import parse_args

    argv = ["-H", str(hostfile), "--launcher", launcher]
    if include:
        argv += ["--include", include]
    argv += ["train.py", "--lr", "0.1"]
    return parse_args(argv)


def test_openmpi_runner_command(tmp_path):
    """--launcher=openmpi builds one mpirun line that starts every RANK
    directly (no per-node spawner) and exports the DS_* rendezvous env
    (reference multinode_runner.py:77-107)."""
    from deepspeed_tpu.launcher.runner import OpenMPIRunner

    hostfile = tmp_path / "hostfile"
    hostfile.write_text("worker-0 slots=2\nworker-1 slots=2\n")
    args = _mpi_args(hostfile, "openmpi")
    # the DERIVED resource set (worker-1 trimmed to 1 slot) must reach
    # mpirun, not the raw user hostfile
    active = {"worker-0": [0, 1], "worker-1": [0]}
    (cmd,) = OpenMPIRunner(args, active, "worker-0").commands()
    assert cmd[:3] == ["mpirun", "-n", "3"]
    derived = cmd[cmd.index("-hostfile") + 1]
    assert derived != str(hostfile)
    with open(derived) as f:
        assert f.read().splitlines() == ["worker-0 slots=2",
                                         "worker-1 slots=1"]
    joined = " ".join(cmd)
    assert "-x DS_COORDINATOR=worker-0:29500" in joined
    assert "-x DS_NUM_PROCESSES=3" in joined
    # ranks run the user script directly under python -u
    assert cmd[-3:] == ["train.py", "--lr", "0.1"]
    assert "deepspeed_tpu.launcher.launch" not in joined
    os.unlink(derived)


def test_mvapich_runner_command(tmp_path):
    from deepspeed_tpu.launcher.runner import MVAPICHRunner

    hostfile = tmp_path / "hostfile"
    hostfile.write_text("a slots=2\nb slots=2\n")
    args = _mpi_args(hostfile, "mvapich")
    (cmd,) = MVAPICHRunner(args, {"a": [0, 1], "b": [0, 1]},
                           "a").commands()
    assert cmd[:5] == ["mpirun", "-np", "4", "-ppn", "2"]
    derived = cmd[cmd.index("--hostfile") + 1]
    with open(derived) as f:
        assert f.read().split() == ["a", "b"]
    # Hydra's -env takes name and value as SEPARATE tokens
    env_pairs = {cmd[i + 1]: cmd[i + 2]
                 for i, tok in enumerate(cmd) if tok == "-env"}
    assert env_pairs["DS_COORDINATOR"] == "a:29500"
    os.unlink(derived)


def test_mpi_runner_rejects_include(tmp_path):
    from deepspeed_tpu.launcher.runner import OpenMPIRunner

    hostfile = tmp_path / "hostfile"
    hostfile.write_text("a slots=2\n")
    args = _mpi_args(hostfile, "openmpi", include="a:0")
    with pytest.raises(AssertionError, match="include"):
        OpenMPIRunner(args, {"a": [0]}, "a")


def test_init_distributed_mpi_env_fallback(monkeypatch):
    """mpirun-scheduled ranks have no DS_PROCESS_ID; rank/size must come
    from the MPI library env (the reference's mpi4py discovery analog)."""
    from deepspeed_tpu.utils.distributed import _resolve_env

    for var in ("DS_NUM_PROCESSES", "DS_PROCESS_ID"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("DS_COORDINATOR", "host0:29500")
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "3")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "8")
    assert _resolve_env() == ("host0:29500", 8, 3)
    # DS_* takes precedence over MPI env when both are set
    monkeypatch.setenv("DS_NUM_PROCESSES", "4")
    monkeypatch.setenv("DS_PROCESS_ID", "1")
    assert _resolve_env() == ("host0:29500", 4, 1)
    # auto_mpi_discovery=False ignores the MPI env entirely
    monkeypatch.delenv("DS_NUM_PROCESSES")
    monkeypatch.delenv("DS_PROCESS_ID")
    assert _resolve_env(mpi=False) == ("host0:29500", 0, None)


def test_collect_exports(tmp_path):
    """Prefix-matched env + .deepspeed_env files travel to workers
    (reference runner.py:27-29, 341-356); file entries need no prefix and
    override inherited env; later files override earlier ones."""
    from deepspeed_tpu.launcher.runner import collect_exports

    environ = {"LIBTPU_INIT_ARGS": "--mega", "JAX_PLATFORMS": "tpu",
               "DS_ANY_FORWARDED_VAR": "1", "HOME": "/root", "PATH": "/bin"}
    assert collect_exports(environ, paths=()) == {
        "LIBTPU_INIT_ARGS": "--mega", "JAX_PLATFORMS": "tpu",
        "DS_ANY_FORWARDED_VAR": "1"}
    d1, d2 = tmp_path / "a", tmp_path / "b"
    d1.mkdir(), d2.mkdir()
    (d1 / ".deepspeed_env").write_text(
        "# comment\nMY_CUSTOM_FLAG=from_file\nJAX_PLATFORMS=cpu\n")
    (d2 / ".deepspeed_env").write_text("MY_CUSTOM_FLAG=second_wins\n")
    out = collect_exports(environ, paths=(str(d1), str(d2)))
    assert out["MY_CUSTOM_FLAG"] == "second_wins"
    assert out["JAX_PLATFORMS"] == "cpu"  # file overrides inherited env
    assert out["LIBTPU_INIT_ARGS"] == "--mega"


def test_remote_commands_carry_exports(tmp_path):
    """pdsh/ssh remote shells get an 'export K=V;' prelude; MPI backends
    put the same vars on the rank env (reference multinode_runner.py)."""
    from deepspeed_tpu.launcher.runner import (OpenMPIRunner, PDSHRunner,
                                               SSHRunner)

    hostfile = tmp_path / "hostfile"
    hostfile.write_text("a slots=1\nb slots=1\n")
    args = _mpi_args(hostfile, "pdsh")
    active = {"a": [0], "b": [0]}
    exports = {"LIBTPU_INIT_ARGS": "--x=1 --y", "DS_MARK": "7"}
    (pdsh_cmd,) = PDSHRunner(args, active, "a", exports).commands()
    assert "export LIBTPU_INIT_ARGS='--x=1 --y'; " in pdsh_cmd[-1]
    assert "export DS_MARK=7; " in pdsh_cmd[-1]
    ssh_cmds = SSHRunner(args, active, "a", exports).commands()
    assert all("export DS_MARK=7; " in c[-1] for c in ssh_cmds)
    args = _mpi_args(hostfile, "openmpi")
    (mpi_cmd,) = OpenMPIRunner(args, active, "a", exports).commands()
    assert "-x DS_MARK=7" in " ".join(mpi_cmd)
    os.unlink(mpi_cmd[mpi_cmd.index("-hostfile") + 1])


def test_env_reaches_spawned_process(tmp_path):
    """End-to-end: a prefix-matched parent env var AND a .deepspeed_env
    entry both reach the worker process through the single-node path."""
    hostfile = tmp_path / "hostfile"
    hostfile.write_text(f"{socket.gethostname()} slots=1\n")
    (tmp_path / ".deepspeed_env").write_text("MY_CUSTOM_FLAG=from_file\n")
    script = tmp_path / "probe.py"
    script.write_text(
        "import os, sys\n"
        "open(sys.argv[1], 'w').write(\n"
        "    os.environ.get('LIBTPU_INIT_ARGS', '?') + '|' +\n"
        "    os.environ.get('MY_CUSTOM_FLAG', '?'))\n")
    out = tmp_path / "probe.out"
    env = dict(os.environ)
    env["LIBTPU_INIT_ARGS"] = "--marker=42"
    env["HOME"] = str(tmp_path)  # hermetic: ignore any real ~/.deepspeed_env
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--hostfile", str(hostfile), str(script), str(out)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert out.read_text() == "--marker=42|from_file"


def _order_guard_loader():
    from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader

    data = [np.zeros((2,), np.float32)] * 8
    return DeepSpeedDataLoader(data, batch_size=4,
                               data_parallel_world_size=2,
                               data_parallel_rank=0)


def test_verify_shared_order_raises_on_divergence(monkeypatch):
    """Mismatched cross-host sample order must raise the RuntimeError
    (silent shard duplication otherwise); matching order must not."""
    import jax
    from jax.experimental import multihost_utils

    loader = _order_guard_loader()
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    # two processes reporting DIFFERENT fingerprints
    monkeypatch.setattr(
        multihost_utils, "process_allgather",
        lambda fp: np.stack([np.asarray(fp), np.asarray(fp) + 1]))
    with pytest.raises(RuntimeError, match="order drift"):
        loader._verify_shared_order(np.arange(8))
    # identical fingerprints: no raise
    monkeypatch.setattr(
        multihost_utils, "process_allgather",
        lambda fp: np.stack([np.asarray(fp), np.asarray(fp)]))
    loader._verify_shared_order(np.arange(8))


def test_verify_shared_order_env_and_epoch_gating(monkeypatch):
    import jax
    from jax.experimental import multihost_utils

    loader = _order_guard_loader()
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(
        multihost_utils, "process_allgather",
        lambda fp: np.stack([np.asarray(fp), np.asarray(fp) + 1]))
    # DS_VERIFY_DATA_ORDER=never skips the collective entirely
    monkeypatch.setenv("DS_VERIFY_DATA_ORDER", "never")
    loader._verify_shared_order(np.arange(8))
    # default epoch0 mode skips past the first epoch (no sync point a
    # dead process could strand the others in)
    monkeypatch.delenv("DS_VERIFY_DATA_ORDER", raising=False)
    loader.epoch = 3
    loader._verify_shared_order(np.arange(8))
    loader.epoch = 1
    with pytest.raises(RuntimeError, match="order drift"):
        loader._verify_shared_order(np.arange(8))
    # world-1 loaders never dial the collective, whatever the env says
    from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader

    solo = DeepSpeedDataLoader([np.zeros((2,), np.float32)] * 8,
                               batch_size=4)
    monkeypatch.setenv("DS_VERIFY_DATA_ORDER", "always")
    solo._verify_shared_order(np.arange(8))


# ---------------------------------------------------------------------------
# resilience exit-code contract (launch.py)
# ---------------------------------------------------------------------------

def _launch_main(tmp_path, script_body, script_args=(), max_restarts=0,
                 extra_argv=()):
    """Drive launch.main() inline with one local child slot; returns the
    SystemExit code."""
    from deepspeed_tpu.launcher import launch
    from deepspeed_tpu.launcher.runner import encode_world_info
    import signal

    script = tmp_path / "child.py"
    script.write_text(script_body)
    wi = encode_world_info({socket.gethostname(): [0]})
    argv = ["--world_info", wi, "--node_rank", "0",
            "--master_addr", "127.0.0.1", "--master_port", "29999",
            "--max-restarts", str(max_restarts), *extra_argv,
            str(script), *script_args]
    old_int = signal.getsignal(signal.SIGINT)
    old_term = signal.getsignal(signal.SIGTERM)
    try:
        with pytest.raises(SystemExit) as exc:
            launch.main(argv)
        return exc.value.code
    finally:
        signal.signal(signal.SIGINT, old_int)
        signal.signal(signal.SIGTERM, old_term)


def test_launch_exports_compile_cache_dir(tmp_path, monkeypatch):
    """--compile-cache-dir reaches children as JAX_COMPILATION_CACHE_DIR
    (absolute), so respawned processes warm-start their compiles — and
    the launcher side stays jax-free (the child env var is jax's native
    knob; nothing is imported to set it)."""
    monkeypatch.setenv("DS_MONITOR_POLL_SECS", "0.1")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    out = tmp_path / "env.out"
    code = _launch_main(
        tmp_path,
        "import os, sys\n"
        "open(sys.argv[1], 'w').write(\n"
        "    os.environ.get('JAX_COMPILATION_CACHE_DIR', '?'))\n",
        script_args=(str(out),),
        extra_argv=("--compile-cache-dir", str(tmp_path / "xla_cache")))
    assert code == 0
    assert out.read_text() == os.path.abspath(str(tmp_path / "xla_cache"))


def test_several_processes_on_one_tpu_host_are_refused(tmp_path, monkeypatch):
    """A chip belongs to one process at a time and the launcher binds no
    chips: > 1 local process on a host with TPU chips fails with the
    supported layout in the message, before anything is spawned — unless
    the children are kept off the TPU (the CPU test fleets)."""
    from deepspeed_tpu.launcher import launch
    from deepspeed_tpu.launcher.runner import encode_world_info

    monkeypatch.setattr(launch, "local_tpu_chips",
                        lambda: ["/dev/accel0", "/dev/accel1"])
    launch.check_one_process_per_tpu_host(1, environ={})
    launch.check_one_process_per_tpu_host(2, environ={"JAX_PLATFORMS": "cpu"})
    for env in ({}, {"JAX_PLATFORMS": "tpu"}, {"JAX_PLATFORMS": "tpu,cpu"}):
        with pytest.raises(RuntimeError, match="ONE process per host"):
            launch.check_one_process_per_tpu_host(2, environ=env)
    # and main() makes the check before it spawns
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    script = tmp_path / "child.py"
    script.write_text("open(%r, 'w').write('spawned')\n"
                      % str(tmp_path / "spawned"))
    wi = encode_world_info({socket.gethostname(): [0, 1]})
    with pytest.raises(RuntimeError, match="2 processes asked for"):
        launch.main(["--world_info", wi, "--node_rank", "0",
                     "--master_addr", "127.0.0.1", "--master_port", "29998",
                     str(script)])
    assert not (tmp_path / "spawned").exists()
    # a host without chips launches as many as asked
    monkeypatch.setattr(launch, "local_tpu_chips", lambda: [])
    launch.check_one_process_per_tpu_host(8, environ={})


def test_map_exit_code_signal_names():
    import signal

    from deepspeed_tpu.launcher.launch import map_exit_code

    assert map_exit_code(0) == (0, None)
    assert map_exit_code(7) == (7, None)
    assert map_exit_code(-signal.SIGKILL) == (137, "SIGKILL")
    assert map_exit_code(-signal.SIGSEGV) == (139, "SIGSEGV")


def test_launch_maps_child_signal_death(tmp_path, monkeypatch):
    """A child killed by a signal must exit the launcher with 128+signum
    (launch.py used to sys.exit the raw negative poll() value)."""
    monkeypatch.setenv("DS_MONITOR_POLL_SECS", "0.1")
    code = _launch_main(
        tmp_path,
        "import os, signal\nos.kill(os.getpid(), signal.SIGKILL)\n")
    assert code == 137


def test_launch_max_restarts_recovers_flaky_child(tmp_path, monkeypatch):
    """--max-restarts respawns a failed child with backoff; a child that
    succeeds on its second life exits the node cleanly."""
    monkeypatch.setenv("DS_MONITOR_POLL_SECS", "0.1")
    monkeypatch.setenv("DS_RESTART_BACKOFF_SECS", "0.05")
    marker = tmp_path / "ran_once"
    code = _launch_main(
        tmp_path,
        "import os, sys\n"
        "marker = sys.argv[1]\n"
        "if os.path.exists(marker):\n"
        "    sys.exit(0)\n"
        "open(marker, 'w').write('x')\n"
        "sys.exit(1)\n",
        script_args=(str(marker),), max_restarts=1)
    assert code == 0
    assert marker.exists()


def test_launch_poison_exit_code_never_respawns(tmp_path, monkeypatch):
    """A divergence abort must tear the node down immediately even with
    restart budget left — respawning replays the same divergence."""
    from deepspeed_tpu.resilience import EXIT_DIVERGENCE_ABORT

    monkeypatch.setenv("DS_MONITOR_POLL_SECS", "0.1")
    monkeypatch.setenv("DS_RESTART_BACKOFF_SECS", "0.05")
    counter = tmp_path / "runs"
    code = _launch_main(
        tmp_path,
        "import sys\n"
        "with open(sys.argv[1], 'a') as f:\n"
        "    f.write('x')\n"
        f"sys.exit({EXIT_DIVERGENCE_ABORT})\n",
        script_args=(str(counter),), max_restarts=3)
    assert code == EXIT_DIVERGENCE_ABORT
    assert counter.read_text() == "x"   # ran exactly once


def test_dataloader_order_fingerprint():
    """The multi-host order-drift guard's fingerprint: deterministic,
    order-sensitive, and cheap (weak spot: silent shard duplication when
    processes iterate in different orders)."""
    from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader

    a = DeepSpeedDataLoader.order_fingerprint(np.arange(64))
    b = DeepSpeedDataLoader.order_fingerprint(np.arange(64))
    assert a == b
    shuffled = np.arange(64)[::-1].copy()
    assert DeepSpeedDataLoader.order_fingerprint(shuffled) != a
    # single-process: the verify hook is a no-op (no collective dialed)
    loader = DeepSpeedDataLoader(
        [np.zeros((2,), np.float32)] * 8, batch_size=4, shuffle=True, seed=1)
    assert len(list(loader)) == 2
