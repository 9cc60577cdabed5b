"""Launcher subsystem (reference: tests/unit/test_run.py — arg/hostfile
handling — plus an end-to-end 2-process CPU launch the reference can't do in
unit tests; here gloo-backed jax.distributed makes it cheap)."""

import os
import socket
import subprocess
import sys
import textwrap
import time

import pytest

from deepspeed_tpu.launcher import runner as runner_lib
from deepspeed_tpu.launcher.launch import global_rank_mapping

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------------- unit math

def test_fetch_hostfile(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("# cluster\nworker-0 slots=4\nworker-1 slots=2\n\n")
    res = runner_lib.fetch_hostfile(str(hf))
    assert res == {"worker-0": 4, "worker-1": 2}


def test_fetch_hostfile_rejects_dup(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("w0 slots=2\nw0 slots=4\n")
    with pytest.raises(ValueError):
        runner_lib.fetch_hostfile(str(hf))


def test_include_exclude_filters():
    res = {"w0": 4, "w1": 4, "w2": 4}
    inc = runner_lib.parse_inclusion_exclusion(res, "w0@w1:0,2", "")
    assert inc == {"w0": [0, 1, 2, 3], "w1": [0, 2]}
    exc = runner_lib.parse_inclusion_exclusion(res, "", "w2@w1:3")
    assert exc == {"w0": [0, 1, 2, 3], "w1": [0, 1, 2]}
    with pytest.raises(ValueError):
        runner_lib.parse_inclusion_exclusion(res, "w0", "w1")
    with pytest.raises(ValueError):
        runner_lib.parse_inclusion_exclusion(res, "w9", "")
    with pytest.raises(ValueError):
        runner_lib.parse_inclusion_exclusion(res, "w0:7", "")


def test_world_info_roundtrip():
    wi = {"w0": [0, 1], "w1": [0]}
    enc = runner_lib.encode_world_info(wi)
    assert runner_lib.decode_world_info(enc) == wi


def test_global_rank_mapping():
    wi = {"w0": [0, 1], "w1": [0, 1, 2]}
    m = global_rank_mapping(wi)
    assert m == {"w0": [0, 1], "w1": [2, 3, 4]}


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _clean_env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # children get 1 CPU device each
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    for k in ("COORDINATOR_ADDRESS", "NUM_PROCESSES", "PROCESS_ID",
              "LOCAL_RANK"):
        env.pop(k, None)
    return env


TRAINER = textwrap.dedent("""
    import os, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from deepspeed_tpu import comm

    comm.init_distributed()
    assert jax.process_count() == 2, jax.process_count()
    rank = jax.process_index()
    assert rank == int(os.environ["PROCESS_ID"])

    mesh = Mesh(np.array(jax.devices()), ("dp",))
    rng = np.random.default_rng(rank)
    p = jnp.zeros((8,), jnp.float32)          # replicated params
    w_true = jnp.arange(1.0, 9.0, dtype=jnp.float32) / 8.0

    @jax.jit
    def step(p, x, y):
        def loss_fn(p):
            return jnp.mean((x @ p - y) ** 2)
        l, g = jax.value_and_grad(loss_fn)(p)
        return p - 0.1 * g, l

    sh = NamedSharding(mesh, P("dp"))
    losses = []
    for i in range(40):
        xl = rng.normal(size=(4, 8)).astype(np.float32)
        x = jax.make_array_from_process_local_data(sh, xl)
        y = jax.make_array_from_process_local_data(
            sh, np.asarray(xl @ np.asarray(w_true)))
        p, l = step(p, x, y)
        losses.append(float(jax.device_get(l)))
    assert losses[-1] < losses[0] * 0.5, losses
    print(f"rank {rank} converged: {losses[0]:.4f} -> {losses[-1]:.4f}",
          flush=True)
""")

FAILER = textwrap.dedent("""
    import os, sys, time
    if os.environ["PROCESS_ID"] == "1":
        time.sleep(0.5)
        sys.exit(3)          # rank 1 dies
    time.sleep(600)          # rank 0 would hang forever without the babysitter
""")


def test_launcher_two_process_convergence(tmp_path):
    """ds_tpu-style launch of 2 processes on localhost: env forwarding, gloo
    rendezvous via COORDINATOR_ADDRESS, cross-process dp collective, loss
    converges in both ranks."""
    script = tmp_path / "trainer.py"
    script.write_text(TRAINER)
    port = _free_port()
    proc = subprocess.run(
        [sys.executable, "-u", "-m", "deepspeed_tpu.launcher.runner",
         "--num_procs", "2", "--master_port", str(port),
         str(script)],
        env=_clean_env(), capture_output=True, text=True, timeout=150,
        cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.count("converged") == 2, proc.stdout + proc.stderr


def test_runner_rejects_missing_explicit_hostfile(tmp_path):
    with pytest.raises(FileNotFoundError):
        runner_lib.main(["--hostfile", str(tmp_path / "nope"), "x.py"])


def test_babysitter_kills_siblings(tmp_path):
    """One failing rank must take down the whole node job with its exit
    code (reference launch.py:176-214) — rank 0 sleeps 600s, so anything
    under the timeout proves it was killed."""
    script = tmp_path / "failer.py"
    script.write_text(FAILER)
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, "-u", "-m", "deepspeed_tpu.launcher.launch",
         f"--world_info={runner_lib.encode_world_info({'localhost': [0, 1]})}",
         "--node_rank=0", "--master_addr=127.0.0.1",
         f"--master_port={_free_port()}", str(script)],
        env=_clean_env(), capture_output=True, text=True, timeout=90,
        cwd=REPO)
    assert proc.returncode == 3, proc.stdout + proc.stderr
    assert time.time() - t0 < 60


def test_ds_report_runs():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_report")],
        env=_clean_env(), capture_output=True, text=True, timeout=120,
        cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "op compatibility" in proc.stdout
    assert "cpu_adam" in proc.stdout
