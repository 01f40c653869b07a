"""BENCHMARK.json against the contract, and a cell defined by files alone."""

import json
import os
import re

import pytest

from perfbench.manifest import ROOT, Manifest

pytestmark = pytest.mark.tier1

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def man():
    return Manifest()


def test_top_level_keys(man):
    assert set(man.doc) == {"command", "paths", "run_seconds", "configs",
                            "workloads", "end_to_end", "per_layer"}
    assert 1 <= man.doc["run_seconds"] <= 51
    assert isinstance(man.doc["run_seconds"], int)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert len(man.doc["command"]) <= 32
    for p in man.doc["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))


def test_every_name_and_unit_is_well_formed(man):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [e["name"] for e in man.doc[group]]
        assert len(seen) == len(set(seen)), group
        names += seen
    for w in man.doc["workloads"]:
        names += [w["config"], w["traffic"]]
    for c in man.doc["configs"]:
        names += c["reduced"]
    for n in names:
        assert NAME.match(n), n
    metrics = man.doc["end_to_end"] + man.doc["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_entries_have_exactly_the_contract_keys(man):
    for c in man.doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in man.doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in man.doc["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in man.doc["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_four_chip_cells_are_at_most_a_quarter(man):
    four = [w for w in man.doc["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(man.doc["workloads"]) // 4)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric(man):
    assert any(m["name"] == "setup_s" for m in man.doc["end_to_end"])
    for w in man.doc["workloads"]:
        e2e = {m["name"] for m in man.metrics_for(w["name"], "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert man.metrics_for(w["name"], "per_layer"), w["name"]


def test_every_layer_metric_moves_a_metric_its_cells_report(man):
    cells = {w["name"] for w in man.doc["workloads"]}
    e2e = {m["name"]: m for m in man.doc["end_to_end"]}
    for m in man.doc["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        target = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells, (m["name"], cell)
            assert cell in target.get("workloads", cells), (m["name"], cell)
    for m in man.doc["end_to_end"]:
        for cell in m.get("workloads", []):
            assert cell in cells


def test_every_file_a_cell_names_exists(man):
    used = set()
    for w in man.doc["workloads"]:
        cfg = man.config(w["config"])
        used.add(w["config"])
        assert cfg["source"] == man.config_entry(w["config"])["source"]
        assert cfg["reduced"] == man.config_entry(w["config"])["reduced"]
        tr = man.traffic(w["traffic"])
        assert hasattr(man.runner(tr["kind"]), "run")
        for group in ("end_to_end", "per_layer"):
            for m in man.metrics_for(w["name"], group):
                spec = man.metric_file(m["name"])
                assert spec["unit"] == m["unit"]
                assert hasattr(man.reducer(spec["reducer"]), "reduce")
    assert used == {c["name"] for c in man.doc["configs"]}
    files = [c["file"] for c in man.doc["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert any(f.startswith(p + "/") for p in man.doc["paths"])


def test_no_width_is_reduced(man):
    widths = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                        r"head_dim|d_model|d_ff|expansion|per_tok")
    for c in man.doc["configs"]:
        for key in c["reduced"]:
            assert not widths.search(key), (c["name"], key)
        cfg = man.config(c["name"])
        pub, m = cfg["published"], cfg["model"]
        if "hidden_size" in pub:  # starcoder2's config.json keys
            assert (m["d_model"], m["d_ff"], m["n_heads"], m["n_kv_heads"],
                    m["vocab"], m["n_layers"]) == (
                pub["hidden_size"], pub["intermediate_size"],
                pub["num_attention_heads"], pub["num_key_value_heads"],
                pub["vocab_size"], pub["num_hidden_layers"])
        else:  # gpt2's
            assert (m["d_model"], m["d_ff"], m["n_heads"], m["vocab"],
                    m["n_layers"], m["max_len"]) == (
                pub["n_embd"], pub["n_inner"], pub["n_head"],
                pub["vocab_size"], pub["n_layer"], pub["n_positions"])


def test_a_fifth_cell_is_files_and_entries_only(tmp_path):
    """A new configuration, traffic mix, runner kind, per-layer metric and
    reducer, all in a directory of their own: the harness finds each by
    name and nothing of ``perfbench/`` is edited."""
    data = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "reducers", "runners"):
        (data / sub).mkdir(parents=True)
    (data / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "source": "https://example.org/toy", "reduced": []}))
    (data / "traffic" / "pings.json").write_text(json.dumps(
        {"kind": "ping", "count": 7}))
    (data / "runners" / "ping.py").write_text(
        "def run(ctx):\n"
        "    n = ctx.traffic['count']\n"
        "    return {'correct': True, 'attempted': n, 'failed': 0,\n"
        "            'values': {'pings': float(n)}, 'facts': {},\n"
        "            'compared': [], 'memory_peak_bytes': 0,\n"
        "            'counts': {'pings': n}}\n")
    (data / "reducers" / "double.py").write_text(
        "def reduce(facts, args):\n"
        "    return 2 * facts['values'][args['key']]\n")
    (data / "metrics" / "pings_twice.json").write_text(json.dumps(
        {"name": "pings_twice", "unit": "pings", "reducer": "double",
         "args": {"key": "pings"}}))
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    doc["configs"].append({"name": "toy", "source": "https://example.org/toy",
                           "file": "bench/configs/toy.json", "reduced": [],
                           "why": "x"})
    doc["workloads"].append({"name": "toy_pings", "config": "toy",
                             "traffic": "pings", "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "pings_twice", "unit": "pings",
                             "better": "higher", "source": "program_counter",
                             "layer": "toy", "moves": "setup_s",
                             "workloads": ["toy_pings"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    man = Manifest(str(tmp_path), str(data))
    w = man.workload("toy_pings")
    tr = man.traffic(w["traffic"])
    assert man.config(w["config"])["name"] == "toy"

    class Ctx:
        traffic = tr

    res = man.runner(tr["kind"]).run(Ctx)
    [m] = man.metrics_for("toy_pings", "per_layer")
    spec = man.metric_file(m["name"])
    assert man.reducer(spec["reducer"]).reduce(
        {"values": res["values"]}, spec["args"]) == 14.0
    # the package's own runners and reducers are still found from there
    assert hasattr(man.runner("decode_backlog"), "run")
    assert hasattr(man.reducer("value"), "reduce")


def test_peaks_table_has_the_v5e_and_refuses_the_unknown():
    from perfbench import device

    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["interconnect_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        device.peaks("cpu")
