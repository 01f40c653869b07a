"""``perfbench.run`` end to end at the rehearsal size, one case per runner
kind, and its refusals: no TPU, no program."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import run as prun
from perfbench.manifest import ROOT, Manifest

pytestmark = pytest.mark.tier1

#: Every case here that traces and compiles a model on the eight virtual
#: devices is `slow`: run beside the serving tests that assert latencies
#: (tests/serving_tests/test_serve_obs.py) under six xdist workers on the
#: 8-core sandbox, they made one of those fail in 5 full runs of 6, and none
#: failed in 3 of 3 without them.  `pytest -m slow tests/perfbench_tests`
#: runs them (about four minutes).
heavy = pytest.mark.slow


def _lines(capsys):
    return [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith("{")]


def _one_cell_per_kind():
    man = Manifest()
    seen = {}
    for w in man.doc["workloads"]:
        seen.setdefault(man.traffic(w["traffic"])["kind"], w["name"])
    return [pytest.param(kind, cell, marks=heavy)
            for kind, cell in sorted(seen.items())]


@pytest.mark.parametrize("kind,cell", _one_cell_per_kind())
def test_rehearsal_runs_and_prints_no_device_metric(kind, cell, capsys):
    rc = prun.main(["--workload", cell, "--seed", str(2**31 + 17),
                    "--seconds", "1.5", "--rehearse"])
    lines = _lines(capsys)
    last = lines[-1]
    assert rc == 0 and last["rehearsal"] is True and last["correct"] is True
    assert "metrics" not in last and "breakdown" not in last
    assert set(last["device"]) == {"platform"}
    assert last["attempted"] > 0 and last["failed"] == 0
    compared = [x["compared"] for x in lines if "compared" in x]
    assert compared and all("limit" in c and "value" in c
                            for c in compared if "number" in c)


@pytest.mark.parametrize("seconds,left_min,closed_on", [
    ("0.02", 0, "clock"), ("30", 0, "empty"), ("30", 2, "empty")])
def test_a_backlog_run_says_how_its_window_closed(seconds, left_min,
                                                  closed_on, tmp_path,
                                                  capsys):
    """``info.closed_on`` / ``info.queue_left``, and again in the last line
    (``window``): on the clock with requests still queued, or on an empty
    queue — which the mix's ``queue_left_min`` makes a run that is not
    correct (``queue_short``, the first number compared), since slots with
    nothing to refill from do not run the cell's load."""
    tr = Manifest().traffic("decode_backlog")
    tr["rehearse"]["queue_left_min"] = left_min
    os.makedirs(tmp_path / "traffic")
    (tmp_path / "traffic" / "decode_backlog.json").write_text(json.dumps(tr))
    rc = prun.main(["--workload", "gpt2-xl_serve_backlog", "--seed", "5",
                    "--seconds", seconds, "--rehearse",
                    "--data", str(tmp_path)])
    out = capsys.readouterr()
    lines = [json.loads(x) for x in out.out.splitlines() if x.startswith("{")]
    info = next(x["info"] for x in lines if "info" in x)
    last = lines[-1]
    assert info["closed_on"] == closed_on == last["window"]["closed_on"]
    assert info["queue_left"] == last["window"]["queue_left"]
    assert (info["queue_left"] > 0) == (closed_on == "clock")
    short = last["compared"]["queue_short"]
    assert short == {"value": float(max(0, left_min - info["queue_left"])),
                     "limit": 0.0}
    assert last["correct"] is (short["value"] == 0.0) and rc == (
        0 if last["correct"] else 1)
    assert "perfbench: compared queue_short" in out.err
    # every tick of the window is the replay's, whatever the clock did
    assert info["schedule"]["off"] == 0
    assert info["schedule"]["fill"] == info["schedule"]["fill_replay"]
    assert info["schedule"]["ticks"] == info["ticks"]


def test_without_a_tpu_the_command_fails_and_prints_no_result(capsys):
    cell = Manifest().doc["workloads"][0]["name"]
    rc = prun.main(["--workload", cell, "--seed", "1", "--seconds", "1",
                    "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out.strip() == ""
    assert "TPU" in out.err


@heavy
def test_in_a_directory_with_the_benchmark_alone_it_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in Manifest().doc["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    cell = Manifest().doc["workloads"][0]["name"]
    for extra in ([], ["--rehearse"]):  # no TPU; and no program to import
        r = subprocess.run(
            [sys.executable, "-m", "perfbench.run", "--workload", cell,
             "--seed", "1", "--seconds", "1"] + extra,
            cwd=tmp_path, env=env, capture_output=True, text=True,
            timeout=120)
        assert r.returncode != 0
        assert '"correct"' not in r.stdout
    assert "chainermn_tpu" in r.stderr


LEFT_OUT = {
    # cell: (config, traffic, chips, its end-to-end metrics, per-layer metrics)
    "sc2-3b_train_dp4": (
        "starcoder2-3b", "train_steps", 4, ["train_tokens_per_s"],
        ["allreduce_exposed_ms", "train_mfu", "train_device_idle_pct"]),
    "gpt2-xl_serve_chat": (
        "gpt2-xl", "open_loop", 1, ["ttft_p90_ms", "gap_p95_ms"],
        ["queue_wait_p90_ms", "prefill_ms_per_req", "gen_late_p95_ms",
         "tick_ms.chat", "chat_device_idle_pct"]),
}


@pytest.mark.parametrize("cell", [pytest.param(c, marks=heavy)
                                  for c in sorted(LEFT_OUT)])
def test_a_cell_left_out_of_the_manifest_is_entries_alone(cell, tmp_path,
                                                          capsys):
    """The two cells PR 23 could not prove on the chip (PERF.md, Open
    questions) need manifest entries only: configuration, traffic mix,
    runner, metric files and reducers are all there, and the whole path runs
    here at the rehearsal size (the four-chip one on four virtual devices,
    against the four-row reference)."""
    import jax

    config, traffic, chips, e2e, layer = LEFT_OUT[cell]
    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} devices")
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    doc["workloads"].append({"name": cell, "config": config,
                             "traffic": traffic, "chips": chips, "why": "x"})
    known = {m["name"]: m for m in doc["end_to_end"] + doc["per_layer"]}
    for group, names in (("end_to_end", e2e), ("per_layer", layer)):
        for name in names:
            if name in known:
                known[name]["workloads"].append(cell)
                continue
            spec = Manifest().metric_file(name)
            entry = {"name": name, "unit": spec["unit"], "better": "lower",
                     "source": "host_clock", "workloads": [cell]}
            if group == "end_to_end":
                entry["bound"] = 0.1
            else:
                entry.update(layer=spec["layer"], moves=spec["moves"])
            doc[group].append(entry)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    os.makedirs(tmp_path / "perfbench" / "configs")
    shutil.copy(os.path.join(ROOT, "perfbench", "configs", f"{config}.json"),
                tmp_path / "perfbench" / "configs")
    man = Manifest(str(tmp_path))
    for group, names in (("end_to_end", e2e), ("per_layer", layer)):
        got = [m["name"] for m in man.metrics_for(cell, group)]
        for name in names:
            assert name in got
            spec = man.metric_file(name)
            assert hasattr(man.reducer(spec["reducer"]), "reduce")
    rc = prun.main(["--workload", cell, "--seed", "9", "--seconds", "1.5",
                    "--rehearse", "--root", str(tmp_path)])
    last = _lines(capsys)[-1]
    assert rc == 0 and last["correct"] is True and last["rehearsal"] is True
