"""Multi-process launcher — the ``mpiexec`` analog.

Reference jobs start as ``mpiexec -n N python train.py``: the MPI runtime
spawns the ranks, wires their bootstrap, and — crucially for fault tolerance
— kills every rank when one calls ``MPI_Abort`` (which the global except
hook does on an uncaught exception).  JAX has no launcher daemon; this
module is that missing runtime piece for local/single-host multi-process
runs (the torchrun shape):

    python -m chainermn_tpu.launch --nproc 2 train.py --epochs 4

It allocates the coordinator and object-plane ports, exports the bootstrap
env (``CMN_COORDINATOR`` / ``CMN_NUM_PROCESSES`` / ``CMN_PROCESS_ID`` /
``CMN_TPU_HOSTS`` / ``CMN_TPU_RANK``) consumed by
:func:`chainermn_tpu.init_distributed`, and supervises the children: the
FIRST nonzero exit tears the remaining ranks down (SIGTERM, then SIGKILL
after a grace period) — a peer blocked in a collective whose partner died
is exactly the deadlock the reference's ``MPI_Abort`` existed to prevent.

Multi-host jobs don't launch through this (each host runs one process under
its own supervisor and passes an explicit coordinator address); the kill-on
-failure contract there belongs to the cluster scheduler, as it did to the
multi-host MPI runtime.

One process per chip: an accelerator belongs to the first process that
initialises a JAX backend on it, so this parent must never do so.  It
imports the package (and hence ``jax``) for the exit-code constants only;
nothing on the package's import path calls ``jax.devices()`` or runs a
computation (pinned by ``tests/test_repo_health.py::
test_package_import_initialises_no_backend``), so the ranks it spawns find
their devices free.
"""

from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time

from chainermn_tpu.resilience.guard import HEALTH_EXIT_CODE
from chainermn_tpu.resilience.preemption import PREEMPTION_EXIT_CODE


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _flight_dir(env_extra: dict = None) -> str:
    """Per-attempt flight-record directory exported to every rank as
    ``CMN_OBS_FLIGHT_DIR`` (observability/flight.py).  An explicit value
    (caller env_extra or the launcher's own environment) wins; otherwise
    ``$CMN_OBS_DIR|flightrecords`` / ``attempt<N>`` — per-attempt so a
    supervised relaunch never clobbers the records being debugged."""
    explicit = (env_extra or {}).get(
        "CMN_OBS_FLIGHT_DIR", os.environ.get("CMN_OBS_FLIGHT_DIR")
    )
    if explicit:
        return explicit
    attempt = (env_extra or {}).get(
        "CMN_LAUNCH_ATTEMPT", os.environ.get("CMN_LAUNCH_ATTEMPT", "0")
    )
    return os.path.join(
        os.environ.get("CMN_OBS_DIR", "flightrecords"), f"attempt{attempt}"
    )


def _incident_dir(env_extra: dict = None) -> str:
    """Where this attempt's incident bundles land (observability/
    incident.py): an explicit ``CMN_OBS_INCIDENT_DIR`` wins, else the
    plane's default — ``incidents/`` under the attempt's flight dir."""
    explicit = (env_extra or {}).get(
        "CMN_OBS_INCIDENT_DIR", os.environ.get("CMN_OBS_INCIDENT_DIR")
    )
    if explicit:
        return explicit
    return os.path.join(_flight_dir(env_extra), "incidents")


def launch(
    nproc: int,
    argv: list,
    grace_s: float = 10.0,
    env_extra: dict = None,
) -> int:
    """Spawn ``nproc`` ranks of ``argv``; return the job's exit code
    (0 iff every rank exited 0).  On the first nonzero exit the remaining
    ranks are terminated."""
    coord = _free_port()
    hc_ports = [_free_port() for _ in range(nproc)]
    hosts = ",".join(f"127.0.0.1:{p}" for p in hc_ports)
    # Second port set for the failure detector's dedicated heartbeat mesh
    # (resilience/detector.py): heartbeat frames must not share the data
    # plane's per-source FIFOs with real messages.
    hb_ports = [_free_port() for _ in range(nproc)]
    hb_hosts = ",".join(f"127.0.0.1:{p}" for p in hb_ports)
    flight_dir = _flight_dir(env_extra)

    procs = []
    for pid in range(nproc):
        env = dict(os.environ)
        env.update(env_extra or {})
        env.update(
            {
                "CMN_COORDINATOR": f"127.0.0.1:{coord}",
                "CMN_NUM_PROCESSES": str(nproc),
                "CMN_PROCESS_ID": str(pid),
                "CMN_TPU_HOSTS": hosts,
                "CMN_TPU_RANK": str(pid),
                "CMN_TPU_HB_HOSTS": hb_hosts,
                # Per-attempt flight-record path: a crashed/preempted/
                # escalated rank leaves its black box here (written lazily
                # — the dir only materializes when a record lands).
                "CMN_OBS_FLIGHT_DIR": flight_dir,
            }
        )
        # Own session per rank so the launcher can kill a rank's whole
        # process tree, and ranks never receive the terminal's signals.
        procs.append(
            subprocess.Popen(
                [sys.executable] + argv, env=env, start_new_session=True
            )
        )

    def _killall(signum=None, frame=None):
        for p in procs:
            if p.poll() is None:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except Exception:
                    p.kill()
        if signum is not None:
            sys.exit(128 + signum)

    # The launcher itself being terminated must not orphan the ranks (they
    # would hold inherited pipes open and hang the parent harness).
    prev_term = signal.signal(signal.SIGTERM, _killall)
    prev_int = signal.signal(signal.SIGINT, _killall)

    failed_code = None
    try:
        while True:
            running = [p for p in procs if p.poll() is None]
            for p in procs:
                rc = p.poll()
                if rc is not None and rc != 0 and failed_code is None:
                    failed_code = rc
                    sys.stderr.write(
                        f"[chainermn_tpu.launch] rank exited with {rc}; "
                        f"terminating {len(running)} remaining rank(s)\n"
                    )
            if failed_code is not None:
                break
            if not running:
                return 0
            time.sleep(0.2)

        # Tear down survivors: SIGTERM, grace period, then SIGKILL the
        # whole process group (a rank blocked in a native collective may
        # not service SIGTERM at all).
        for p in procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + grace_s
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=max(deadline - time.time(), 0.1))
                except subprocess.TimeoutExpired:
                    try:
                        os.killpg(p.pid, signal.SIGKILL)
                    except Exception:
                        p.kill()
                    p.wait()
        return failed_code
    finally:
        _killall()
        for p in procs:
            if p.poll() is None:
                p.wait()
        signal.signal(signal.SIGTERM, prev_term)
        signal.signal(signal.SIGINT, prev_int)


def supervise(
    nproc: int,
    argv: list,
    restarts: int = 0,
    backoff_s: float = 1.0,
    grace_s: float = 10.0,
    env_extra: dict = None,
    restart_nproc: int = None,
    preempt_restarts: int = 8,
    health_restarts: int = 2,
) -> int:
    """Run the job, relaunching it up to ``restarts`` times on failure.

    The recovery model is the reference's restart-based one (SURVEY.md
    §2.8): a crashed job is torn down whole, then relaunched; ranks
    ``maybe_load`` the latest complete checkpoint and continue.  With a
    checkpointing training script this turns a transient failure into a
    self-healing run without an external scheduler.  Each attempt gets
    fresh coordinator/object-plane ports (``launch`` allocates per call).

    ``restart_nproc`` makes the recovery ELASTIC — beyond the reference's
    fixed-world restart: relaunch attempts run at a DIFFERENT world size
    (fewer processes after losing hosts, more after regaining them), and
    ranks resume through ``maybe_load_elastic``, which reshards the
    checkpoint to whatever world answers.  Every attempt exports
    ``CMN_LAUNCH_ATTEMPT`` so scripts can tell a fresh start from a
    supervised relaunch.

    **Preemption contract**: a job exiting with
    :data:`~chainermn_tpu.resilience.PREEMPTION_EXIT_CODE` was preempted
    cooperatively — the :class:`PreemptionGuard` already took a
    synchronized emergency checkpoint — so it is ALWAYS restart-eligible:
    it consumes the separate ``preempt_restarts`` allowance, never the
    failure ``restarts`` budget (a preempted job is healthy; it must not
    exhaust the crash budget of a flaky one).

    **Training-health contract**: a job exiting with
    :data:`~chainermn_tpu.resilience.HEALTH_EXIT_CODE` escalated past the
    TrainingHealthGuard's IN-PROCESS recovery (its rollbacks never reach
    this supervisor — they are accounted in the guard's own
    ``[chainermn_tpu.guard]`` health lines) — the state on disk was pruned
    back to the last known-good snapshot, so a relaunch resumes verified
    state.  It consumes the separate ``health_restarts`` allowance: a sick
    job is neither a crashing one (``restarts``) nor a healthy preempted
    one (``preempt_restarts``), and the three budgets must not poach from
    each other.

    Each attempt emits one health line to stderr:
    ``attempt N: nproc=X rc=Y (ok|failure|preemption|health) duration=Zs``.
    """
    attempt = 0
    fail_used = 0
    preempt_used = 0
    health_used = 0
    while True:
        n = nproc if attempt == 0 else (restart_nproc or nproc)
        env = dict(env_extra or {})
        env["CMN_LAUNCH_ATTEMPT"] = str(attempt)
        t0 = time.time()
        rc = launch(n, argv, grace_s=grace_s, env_extra=env)
        kind = (
            "ok" if rc == 0
            else "preemption" if rc == PREEMPTION_EXIT_CODE
            else "health" if rc == HEALTH_EXIT_CODE
            else "failure"
        )
        sys.stderr.write(
            f"[chainermn_tpu.launch] attempt {attempt}: nproc={n} rc={rc} "
            f"({kind}) duration={time.time() - t0:.1f}s\n"
        )
        if rc != 0:
            # Post-mortem pointers: where this attempt's ranks left their
            # flight records (if any rank got far enough to write one)
            # and their incident bundles (`python -m chainermn_tpu.
            # observability.incident report <dir>` renders the newest).
            sys.stderr.write(
                f"[chainermn_tpu.launch] attempt {attempt}: flight records "
                f"(if any) under {_flight_dir(env)}\n"
            )
            sys.stderr.write(
                f"[chainermn_tpu.launch] attempt {attempt}: incident "
                f"bundles (if any) under {_incident_dir(env)}\n"
            )
        if rc == 0:
            return 0
        if rc == PREEMPTION_EXIT_CODE:
            if preempt_used >= preempt_restarts:
                return rc
            preempt_used += 1
            attempt += 1
            sys.stderr.write(
                f"[chainermn_tpu.launch] job preempted (rc={rc}); "
                f"restart {preempt_used}/{preempt_restarts} (preemption "
                f"allowance, n={restart_nproc or nproc}) in {backoff_s:.1f}s\n"
            )
        elif rc == HEALTH_EXIT_CODE:
            if health_used >= health_restarts:
                return rc
            health_used += 1
            attempt += 1
            sys.stderr.write(
                f"[chainermn_tpu.launch] training-health escalation "
                f"(rc={rc}); restart {health_used}/{health_restarts} "
                f"(health allowance, n={restart_nproc or nproc}) in "
                f"{backoff_s:.1f}s\n"
            )
        else:
            if fail_used >= restarts:
                return rc
            fail_used += 1
            attempt += 1
            sys.stderr.write(
                f"[chainermn_tpu.launch] job failed (rc={rc}); "
                f"restart {fail_used}/{restarts} "
                f"(n={restart_nproc or nproc}) in {backoff_s:.1f}s\n"
            )
        time.sleep(backoff_s)


def main():
    ap = argparse.ArgumentParser(
        prog="python -m chainermn_tpu.launch",
        description="mpiexec-analog local multi-process launcher",
    )
    ap.add_argument("--nproc", "-n", type=int, required=True)
    ap.add_argument("--grace", type=float, default=10.0,
                    help="seconds between SIGTERM and SIGKILL on teardown")
    ap.add_argument("--restarts", type=int, default=0,
                    help="relaunch the whole job up to N times on failure "
                         "(restart-based recovery; ranks resume from their "
                         "checkpointer's latest complete snapshot)")
    ap.add_argument("--restart-backoff", type=float, default=1.0,
                    help="seconds to wait before a relaunch")
    ap.add_argument("--restart-nproc", type=int, default=None,
                    help="world size for RELAUNCH attempts (elastic "
                         "restart: resume the checkpoint at a different "
                         "process count via maybe_load_elastic)")
    ap.add_argument("--preempt-restarts", type=int, default=8,
                    help="separate relaunch allowance for cooperative "
                         f"preemptions (exit code {PREEMPTION_EXIT_CODE}: "
                         "the PreemptionGuard already checkpointed); does "
                         "not consume --restarts")
    ap.add_argument("--health-restarts", type=int, default=2,
                    help="separate relaunch allowance for training-health "
                         f"escalations (exit code {HEALTH_EXIT_CODE}: the "
                         "TrainingHealthGuard exhausted in-process "
                         "rollback recovery and pruned the checkpoint "
                         "trail back to known-good state); does not "
                         "consume --restarts")
    ap.add_argument("script", help="python script to run on every rank")
    ap.add_argument("args", nargs=argparse.REMAINDER)
    ns = ap.parse_args()
    sys.exit(
        supervise(
            ns.nproc, [ns.script] + ns.args, restarts=ns.restarts,
            backoff_s=ns.restart_backoff, grace_s=ns.grace,
            restart_nproc=ns.restart_nproc,
            preempt_restarts=ns.preempt_restarts,
            health_restarts=ns.health_restarts,
        )
    )


if __name__ == "__main__":
    main()
