"""Real multi-process integration test (reference test strategy, SURVEY.md §4:
"every distributed test is a real multi-process run" — their ``mpiexec -n 2``,
our two OS processes + ``jax.distributed`` coordinator on localhost).

Exercises, with ``process_count == 2`` for real:
  * ``init_distributed`` (the MPI-bootstrap equivalent),
  * the ``nproc > 1`` object-plane branches (bcast/gather/allgather/allreduce
    via multihost_utils, rank-addressed p2p via the native TCP hostcomm),
  * cross-process eager + in-graph collectives on a 2-process CPU mesh,
  * ``scatter_dataset`` per-process sharding,
  * checkpointer save/restore with both hosts participating.
"""

import json
import os
import socket
import subprocess
import sys

import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
WORKER = os.path.join(REPO, "tests", "multiprocess_tests", "worker_two_process.py")


def test_two_process_integration(tmp_path):
    coord = _free_port()
    hc0, hc1 = _free_port(), _free_port()
    env_base = {
        k: v
        for k, v in os.environ.items()
        # Strip the caller's import path and device flags: each worker is
        # one CPU-only process with ONE device (the parent's 8-device
        # XLA_FLAGS would give every rank 8).
        if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")
    }
    env_base.update(
        {
            "PYTHONPATH": REPO,
            "JAX_PLATFORMS": "cpu",
            "CMN_COORDINATOR": f"127.0.0.1:{coord}",
            "CMN_NUM_PROCESSES": "2",
            "CMN_TPU_HOSTS": f"127.0.0.1:{hc0},127.0.0.1:{hc1}",
            "CMN_TEST_TMP": str(tmp_path),
        }
    )

    procs = []
    outs = []
    logs = []
    try:
        for pid in range(2):
            out = tmp_path / f"verdict_{pid}.json"
            env = dict(env_base)
            env["CMN_PROCESS_ID"] = str(pid)
            env["CMN_TPU_RANK"] = str(pid)
            env["CMN_TEST_OUT"] = str(out)
            procs.append(
                subprocess.Popen(
                    [sys.executable, WORKER],
                    env=env,
                    cwd=REPO,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,
                )
            )
            outs.append(out)

        for p in procs:
            stdout, _ = p.communicate(timeout=240)
            logs.append(stdout.decode(errors="replace"))
    finally:
        # A hung worker must not outlive the test holding its ports open.
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()

    verdicts = []
    for pid, out in enumerate(outs):
        assert out.exists(), (
            f"worker {pid} wrote no verdict; log:\n{logs[pid][-4000:]}"
        )
        verdicts.append(json.loads(out.read_text()))

    for pid, v in enumerate(verdicts):
        assert v.get("status") == "ok", (
            f"worker {pid} failed: {v.get('traceback', v)}\n"
            f"log:\n{logs[pid][-4000:]}"
        )
        for key in (
            "topology",
            "obj_collectives",
            "p2p",
            "eager_allreduce",
            "in_graph_psum",
            "scatter_dataset",
            "cross_host_model_parallel",
            "zero_optimizer",
            "checkpoint",
            "corpus_evaluator",
            "device_prefetch",
            "int8_ef_compression",
            "file_backed_data",
        ):
            assert v.get(key) == "ok", (pid, key, v)
