"""``chip_smoke.py`` can be rehearsed on CPU but can never pass there.

The script is the repo's proof that the main path starts on the attached
TPU; the driver runs it in a chipless sandbox first and requires a failure.
Both ways in are pinned: the rehearsal size runs every phase through the
real entry points and still ends ``"ok": false``; the real size stops right
after the device phase (a GPT-2-small step on CPU would prove nothing).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               # keep the rehearsal's cache out of the checkout
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("XLA_FLAGS", None)  # one CPU device, as on a one-chip machine
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=240,
    )
    lines = [json.loads(x) for x in r.stdout.strip().splitlines()]
    return r, lines


@pytest.mark.parametrize("size", ["tiny", "full"])
def test_chip_smoke_fails_without_a_chip(size, tmp_path):
    r, lines = _run(["--size", size], tmp_path)
    assert r.returncode != 0, (r.stdout, r.stderr)
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    phases = {x["phase"]: x for x in lines[:-1]}
    assert phases["device"]["ok"] is False
    if size == "full":
        assert set(phases) == {"device"}  # nothing else is worth running
        return
    assert set(phases) == {"device", "train", "serve", "compile_cache"}
    train, serve = phases["train"], phases["serve"]
    # Every check a CPU can satisfy holds; only "is it the chip" fails.
    assert train["loss_last"] < train["loss_first"]
    assert train["step_compiles"] == 1 and train["params_on_devices"]
    assert train["tpu_custom_calls"] == 0 and train["ok"] is False
    for arm in ("fused", "einsum", "int8_fused", "int8_einsum"):
        assert serve[arm]["terminated_once"], arm
        assert serve[arm]["decode_compiles"] == 1, arm
    assert serve["fused"]["prefix_hit_tokens"] > 0
    assert serve["bf16_vs_einsum"]["agreement_rate"] == 1.0
    assert serve["ok"] is False
    assert phases["compile_cache"]["dir"] == str(tmp_path / "jax_cache")
