"""Config-5 bring-up: a real 2-process jax.distributed run on CPU.

Two OS processes (coordinator + worker), each with 2 virtual CPU devices,
form one 4-device dp mesh through multihost.initialize/pod_mesh and execute
a sharded train step as one SPMD program, with distinct per-process data and
coordinator-gated IO — the single-host miniature of the v5e-64 launch
(SURVEY.md §7 step 9). The reference has no multi-node compute plane at all;
this is the capability its NCCL/MPI-flavored peers would provide.
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "multihost_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_spmd_train_step():
    addr = f"127.0.0.1:{_free_port()}"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    procs = [
        subprocess.Popen([sys.executable, _WORKER, str(pid), addr],
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True, env=env)
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multihost worker timed out")
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"

    results = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT "):
                _, pid, loss, coord = line.split()
                results[int(pid)] = (float(loss), int(coord))
    assert set(results) == {0, 1}, outs
    # one SPMD program: both processes observe the identical global loss
    assert results[0][0] == results[1][0]
    # exactly the coordinator reports coordinator status
    assert results[0][1] == 1 and results[1][1] == 0


def test_two_process_miner_cli(tmp_path):
    """The real role entry under jax.distributed: two miner processes form
    one fsdp=2 x dp=2 SPMD program (params sharded ACROSS processes), train,
    and exactly the coordinator publishes one delta — the full config-5
    wiring of neurons/common.build (initialize -> pod_mesh -> gated IO ->
    allgather-on-publish)."""
    # pre-publish a base into the shared work dir so the miners' bootstrap
    # takes the fetch path on both processes
    from distributedtraining_tpu.models import gpt2
    from distributedtraining_tpu.transport import LocalFSTransport
    import jax as _jax

    model, _ = gpt2.make_model("tiny")
    LocalFSTransport(str(tmp_path / "artifacts")).publish_base(
        model.init_params(_jax.random.PRNGKey(5)))

    addr = f"127.0.0.1:{_free_port()}"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_PLATFORMS"] = "cpu"
    miner = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "neurons", "miner.py")
    args = [
        "--work-dir", str(tmp_path), "--model", "tiny",
        "--dataset", "synthetic", "--hotkey", "hotkey_0",
        "--batch-size", "4", "--seq-len", "32",
        # send/check at 0s: the push's materialize collective and the pull's
        # coordinator-broadcast fire at EVERY poll site on both processes —
        # the exact desync hazards the synced-decision machinery exists for
        "--max-steps", "4", "--send-interval", "0",
        "--check-update-interval", "0",
        "--checkpoint-interval", "0",
        "--dp", "0", "--fsdp", "2",
        "--multihost-coordinator", addr, "--multihost-processes", "2",
    ]
    procs = [
        subprocess.Popen([sys.executable, miner, *args,
                          "--multihost-id", str(pid)],
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True, env=env)
        for pid in (0, 1)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multihost miner timed out")
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"miner process {pid} failed:\n{out}"
    # exactly one delta artifact, written by the coordinator
    deltas = os.listdir(tmp_path / "artifacts" / "deltas")
    # exactly ONE artifact + ONE base-revision rider: both written once,
    # by the coordinator (CoordinatorGatedTransport gates publish_delta
    # AND publish_delta_meta)
    assert sorted(deltas) == ["hotkey_0.meta.json", "hotkey_0.msgpack"]
