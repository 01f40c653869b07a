"""Benchmark harnesses must keep running (CPU smoke modes).

The headline numbers (BASELINE.md) are produced by `benchmarks/*.py` on the
real chip; nothing else guards those scripts from bit-rot between hardware
windows.  Each runs as a real subprocess in its documented CPU smoke mode
and must emit parseable JSON."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

BENCHES = {
    "lm": ["benchmarks/lm.py", "--smoke"],
    "decode": ["benchmarks/decode.py", "--smoke"],
    "decode_streaming": ["benchmarks/decode.py", "--smoke", "--window",
                         "16", "--rolling", "--rope"],
    "flash_interpret": ["benchmarks/flash_tpu.py", "--interpret-smoke"],
    "seq2seq": ["benchmarks/seq2seq.py", "--smoke"],
    "longcontext": ["benchmarks/longcontext.py", "--smoke"],
    "memory_fitprobe": ["benchmarks/memory.py", "--smoke", "--fitprobe",
                        "--allow-cpu"],
    "observability": ["benchmarks/observability.py", "--smoke"],
}


@pytest.mark.parametrize("name", sorted(BENCHES))
def test_benchmark_smoke(name, tmp_path):
    out_path = tmp_path / f"{name}.json"
    env = dict(os.environ)
    env.update({
        "PYTHONPATH": REPO,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (
            env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        ).strip(),
    })
    res = subprocess.run(
        [sys.executable] + BENCHES[name] + ["--out", str(out_path)],
        cwd=REPO, env=env, capture_output=True, timeout=600,
    )
    log = res.stdout.decode(errors="replace") + res.stderr.decode(
        errors="replace"
    )
    assert res.returncode == 0, f"{name} failed:\n{log[-2000:]}"
    # Smoke modes print a JSON payload even when --out is gated to TPU runs.
    payloads = [
        json.loads(line)
        for line in res.stdout.decode(errors="replace").splitlines()
        if line.strip().startswith("{")
    ]
    assert payloads, log[-1000:]
    assert not any("error" in p for p in payloads), payloads


def test_lm_artifact_disposition():
    """The artifact-landing contract (round-5): land on any measurement or on
    an all-OOM run under --accept-oom; withhold on transients always."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "lm_bench", os.path.join(REPO, "benchmarks", "lm.py")
    )
    lm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lm)
    d = lm.artifact_disposition
    assert d(["flash"], [], False, False)          # measured → land
    assert d(["flash"], ["xla"], False, False)     # partial OOM → land
    assert not d([], ["flash"], False, False)      # all-OOM, no flag → hold
    assert d([], ["flash"], False, True)           # all-OOM fit-probe → land
    assert not d([], [], False, True)              # nothing happened → hold
    assert not d(["flash"], [], True, True)        # transient → always hold
    assert not d([], ["flash"], True, True)
