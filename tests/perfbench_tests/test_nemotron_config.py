"""The ``Nemotron-Labs-TwoTower-30B-A3B-Base-BF16`` configuration: its cut
held to the published file, its block's modules (weight tree, FLOP count,
scopes), and its rehearsal preset through the shipped ``train_steps``
runner."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import program_trace as pt
from perfbench import run as prun
from perfbench import traffic_gen, weights
from perfbench.checks import train as check
from perfbench.lint import cut_problems
from perfbench.manifest import Manifest
from perfbench.runners.train_steps import reference_steps

pytestmark = pytest.mark.tier1

NAME = "Nemotron-Labs-TwoTower-30B-A3B-Base-BF16"
CELL = "nemotron-twotower_train_8k_ep8"
MAN = Manifest()
CFG = MAN.config(NAME)


def test_the_cut_is_sound_and_is_exactly_depth_experts_held_and_vocabulary():
    assert cut_problems(CFG) == []
    assert sorted(CFG["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                      "vocab_size"]
    m, pub = CFG["model"], CFG["published"]
    assert (m["n_layers"], m["experts_held"], m["vocab"]) == (27, 16, 16384)
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (52, 128, 131072)
    for key in ("deployment", "assumed", "departures"):
        assert CFG[key], key
    assert "128" in CFG["deployment"] and "131072" in CFG["deployment"] \
        and "52" in CFG["deployment"]
    assert {"positions", "e_bias", "optimizer", "weights"} <= set(CFG["assumed"])
    assert any("denoiser" in d for d in CFG["departures"])


def test_every_published_key_is_repeated_at_the_top_level_as_it_runs():
    """The catalog's keys at the file's top level: the published value, or
    for the three cuts the value that runs."""
    runs = {"num_hidden_layers": 27, "n_routed_experts": 16,
            "vocab_size": 16384}
    for key, value in CFG["published"].items():
        assert CFG[key] == runs.get(key, value), key


@pytest.mark.parametrize("field,key", sorted(CFG["published_as"].items()))
def test_every_width_is_the_published_one(field, key):
    if key in CFG["reduced"]:
        assert CFG["model"][field] < CFG["published"][key]
    else:
        assert CFG["model"][field] == CFG["published"][key]


def test_router_width_is_the_published_expert_count():
    m = CFG["model"]
    assert m["experts_held"] * m["ep_of"] == CFG["published"]["n_routed_experts"]
    specs = MAN.weights(CFG).param_specs(m)
    routers = [specs[f"block_{i}"]["router"][0]
               for i, k in enumerate(m["layer_kinds"]) if k == "E"]
    assert routers and set(routers) == {(m["d_model"], 128)}
    assert 0 <= m["ep_index"] < m["ep_of"]


def test_layer_string_is_the_published_patterns_first_layers():
    m = CFG["model"]
    assert m["layer_kinds"] == \
        CFG["published"]["hybrid_override_pattern"][:m["n_layers"]]
    assert len(m["layer_kinds"]) == m["n_layers"] == 27
    assert [m["layer_kinds"].count(k) for k in "ME*"] == [12, 11, 4]
    assert m["n_layers"] % CFG["layer_pattern"]["period"] == 0
    assert "of" not in CFG["layer_pattern"]  # the pattern does not repeat


def test_weight_tree_is_the_programs_at_the_published_widths():
    m = CFG["model"]
    model = MAN.program(CFG)(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, **m)
    want = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 256), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    specs = MAN.weights(CFG).param_specs(m)
    got = jax.tree_util.tree_map(lambda s: s[0], specs,
                                 is_leaf=weights._is_spec)
    assert jax.tree_util.tree_map(lambda a: a.shape, want) == got
    assert 2.62e9 < weights.n_params(specs) < 2.64e9


def test_flops_against_a_hand_count():
    """ISSUE 38's arithmetic: a token's forward at T = 8192."""
    flops = MAN.flops(CFG)
    m = CFG["model"]
    per = flops.layer_flops_per_token(m, 8192)
    D = 2688
    mamba = 2 * D * (4096 + 6144 + 64) + 2 * 4096 * D \
        + 2 * 128 * 128 * 8 + 3 * 2 * 128 * 64 * 64
    attn = 2 * D * (4096 + 2 * 256) + 2 * 4096 * D \
        + 2 * 2 * 128 * 32 * 8193 / 2
    experts = 2 * D * 128 + 2 * 2 * D * 3712 + 0.75 * 2 * 2 * D * 1856
    head = 2 * D * 16384
    assert per == {"M": mamba, "*": attn, "E": experts, "head": head}
    assert round(mamba / 1e6, 1) == 80.8 and round(attn / 1e6, 1) == 113.9
    assert round(experts / 1e6, 1) == 55.6 and round(head / 1e6) == 88
    total = 12 * mamba + 4 * attn + 11 * experts + head
    assert flops.train_flops_per_token(m, 8192) == 3 * total
    assert round(total / 1e9, 2) == 2.12
    # the three kinds' shares of the model FLOPs, as the cell's `why` has them
    assert round(100 * (12 * mamba + 11 * experts) / total) == 74


def test_grouped_matmul_need_is_the_expected_rows_times_one_matrix():
    need = MAN.need("grouped_matmul")({"config": CFG}, 3)
    rows = 8192 * 6 / 8
    assert need["flops"] == 3 * 2 * rows * 2688 * 1856
    assert need["bytes"] == 3 * 2 * (rows * (2688 + 1856) + 16 * 2688 * 1856)
    # at the bandwidth ridge: 0.31 ms of FLOPs against 0.26 ms of bytes
    assert 1.0 < (need["flops"] / 197e12) / (need["bytes"] / 819e9) < 1.3
    spec = MAN.metric_file("grouped_matmul_roofline")
    names = ["grouped_matmul.4", "%grouped_matmul.12", "grouped_matmul_dw.3",
             "transpose_jvp_grouped_matmul__.2"]
    hits = [[bool(re.search(k["pattern"], n)) for n in names]
            for k in spec["args"]["kernels"]]
    assert hits == [[True, True, False, False], [False, False, True, False]]


def _rehearsal():
    cfg = dict(CFG, **CFG["rehearse"])
    return cfg, cfg["model"]


def test_rehearsal_preset_has_every_layer_kind_and_holds_half_the_experts():
    _, m = _rehearsal()
    assert set(m["layer_kinds"][:m["n_layers"]]) == set("M*E")
    assert (m["experts_held"], m["ep_of"], m["experts_per_tok"]) == (8, 2, 2)
    assert set(m) == set(CFG["model"])


def test_every_scope_of_the_file_is_in_the_lowered_step():
    """As ``test_program_scopes.py`` pins the shipped ones: the scopes the
    file lists (and the shipped tokens this block uses) are ``op_name`` s of
    the train step's program, forward, rematerialised and backward."""
    import chainermn_tpu as cmn

    cfg, m = _rehearsal()
    optim, _ = MAN.optimizer(cfg["train"]["optimizer"])
    comm = cmn.create_communicator("xla", devices=jax.devices()[:1])
    model = MAN.program(cfg)(dtype=jnp.float32, param_dtype=jnp.float32, **m)
    opt = cmn.create_multi_node_optimizer(
        optim.make(cfg["train"]["learning_rate"]), comm)
    state = opt.init(weights.make_params(
        MAN.weights(cfg).param_specs(m), 3, jnp.float32))
    step = opt.make_train_step(
        MAN.loss(cfg)(model, chunk_size=cfg["train"]["ce_chunk"]),
        has_aux=True)
    T = cfg["train"]["seq_len"]
    batch = (jnp.zeros((1, T), jnp.int32), jnp.zeros((1, T), jnp.int32))
    text = step.lower(state, batch).as_text(debug_info=True)
    assert CFG["scopes"] == ["ssm.in_proj", "ssm.conv", "ssm.scan",
                             "ssm.gate_out", "moe.route", "moe.shared",
                             "rms_norm"]
    for scope in CFG["scopes"] + [
            "moe.dispatch", "moe.experts", "moe.combine", "attn_qkv",
            "attn.xla", "attn_out", "embed", "ce", "loss_and_grad",
            "optimizer_update", "apply_updates", "rematted_computation",
            "transpose(jvp("]:
        assert scope in text, scope
    for scope in ("ffn", "attn.flash", "attn.paged", "kv_write"):
        assert not pt.token_regex(re.escape(scope)).search(text), scope


def test_rehearsal_goes_through_the_shipped_runner(capsys):
    rc = prun.main(["--workload", CELL, "--seed", str(2**31 + 21),
                    "--seconds", "1.0", "--rehearse"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert rc == 0 and last["rehearsal"] is True and last["correct"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    assert set(last["compared"]) == {"loss_rel_gap", "grad_norm_rel_gap",
                                     "param_change_rel_gap"}
    info = next(x["info"] for x in lines if "info" in x)
    assert info["step_compiles"] == 1 and info["recompiled_in_window"] == 0


def test_fp8_control_fails_the_rehearsal_limits_and_float32_does_not():
    """The reference put in the program's place, computed in the precision
    below: over the limit of ``grad_norm_rel_gap``."""
    cfg, m = _rehearsal()
    tcfg = cfg["train"]
    rows = traffic_gen.markov_rows(4, tcfg["seq_len"], m["vocab"], 5)
    ref = reference_steps(MAN, cfg, 5, jnp.float32, rows, 2, 1)
    again = reference_steps(MAN, cfg, 5, jnp.float32, rows, 2, 1)
    low = reference_steps(MAN, cfg, 5, jnp.float32, rows, 2, 1, quant="fp8")
    limits = cfg["check"]["train"]
    same, _ = check.numbers(again, ref)
    nums, _ = check.numbers(low, ref)
    assert same["grad_norm_rel_gap"] == 0.0
    assert nums["grad_norm_rel_gap"] > 10 * limits["grad_norm_rel_gap"]
    assert np.isfinite(nums["loss_rel_gap"])


def test_the_cells_limits_hold_the_loss_and_only_routed_and_skip_leaves_are_loose():
    """Every number the check computes has a limit (``loss_rel_gap`` among
    them: PERF.md section 6 has what a fault reads); the leaves judged apart
    are the three that routing reaches in every ``E`` layer and the skip
    ``D`` of every ``M`` layer, nothing else."""
    limits = CFG["check"]["train"]
    assert {k for k in limits if k.endswith("_gap")} == {
        "loss_rel_gap", "grad_norm_rel_gap", "loose_grad_norm_rel_gap",
        "param_change_rel_gap"}
    # never under twice the largest sound reading (PERF.md section 6)
    assert limits["grad_norm_rel_gap"] >= 2 * 2.89e-3
    assert 2 * 2.22e-3 <= limits["loss_rel_gap"] <= 8.3e-2 / 3
    kinds = CFG["model"]["layer_kinds"][:CFG["model"]["n_layers"]]
    want = [f"block_{i}/{leaf}" for i, k in enumerate(kinds) if k == "E"
            for leaf in ("router", "experts_up", "experts_down")]
    want += [f"block_{i}/D" for i, k in enumerate(kinds) if k == "M"]
    assert limits["loose_leaves"] == want
    leaves = weights.make_params(
        MAN.weights(CFG).param_specs(_rehearsal()[1]), 1, jnp.float32)
    assert {"router", "experts_up", "experts_down", "shared_up",
            "shared_down", "norm"} == set(leaves["block_1"])


def test_a_step_that_returns_its_state_unchanged_fails_this_cell(monkeypatch,
                                                                 capsys):
    """The kept fault of ``test_control.py`` through this configuration's
    rehearsal: ``param_change_rel_gap`` reads 1 and ``correct`` is false."""
    from chainermn_tpu.optimizers import MultiNodeOptimizer

    real = MultiNodeOptimizer.make_train_step

    def stuck(self, loss_fn, **kw):
        step = real(self, loss_fn, **dict(kw, donate=False))

        def wrapped(state, batch):
            _, metrics = step(state, batch)
            return state, metrics

        wrapped._cache_size = step._cache_size
        return wrapped

    monkeypatch.setattr(MultiNodeOptimizer, "make_train_step", stuck)
    rc = prun.main(["--workload", CELL, "--seed", "3", "--seconds", "0.5",
                    "--rehearse"])
    last = [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith("{")][-1]
    assert rc != 0 and last["correct"] is False
    assert last["compared"]["param_change_rel_gap"]["value"] > 0.9


def test_flash_need_reads_the_models_head_dim_and_counts_a_backward_by_dq():
    from perfbench.flops import flash_attention

    facts = {"config": CFG}
    fwd = MAN.need("flash_head_dim_forward")(facts, 4)
    bwd = MAN.need("flash_head_dim_backward")(facts, 4)
    one = flash_attention.forward(1, 32, 2, 8192, 128)
    assert fwd == {k: 4 * v for k, v in one.items()}
    assert fwd["flops"] == 4 * 2 * 2 * 128 * (8192 * 8193 / 2) * 32
    assert bwd["flops"] == 2.5 * fwd["flops"]
    assert MAN.need("flash_head_dim_time_only")(facts, 8) == {
        "flops": 0.0, "bytes": 0.0}
    spec = MAN.metric_file("flash_head_dim_roofline")
    names = ["flash_fwd.3", "%flash_bwd_dq.7", "flash_bwd_dkv.2",
             "flash_bwd_dkv.9"]
    hits = [[bool(re.search(k["pattern"], n)) for n in names]
            for k in spec["args"]["kernels"]]
    assert hits == [[True, False, False, False], [False, True, False, False],
                    [False, False, True, True]]
    assert all(set(k) == {"pattern", "need"}  # one event a call
               for k in spec["args"]["kernels"])


def test_flash_roofline_by_dq_events_reads_a_stated_and_a_derived_head_size_alike():
    """``flash_head_dim_roofline`` is the one flash roofline since PR 43
    (``flash_roofline`` divided the backward's events by 3 where a call has
    been 2 since PR 36): on the recorded training trace it reads
    ``starcoder2-3b``, whose model states no ``head_dim``, as it reads the
    same model with ``head_dim`` 128 stated — 9 matmul units a layer and
    step over the three kernels' time."""
    import os

    from perfbench import trace as ptrace
    from perfbench.manifest import HERE

    t = ptrace.load(os.path.join(HERE, "fixtures",
                                 "train_2steps_program.xplane.pb.gz"))
    peaks = json.load(open(os.path.join(HERE, "peaks.json")))["TPU v5 lite"]
    cfg = MAN.config("starcoder2-3b")
    stated = dict(cfg, model=dict(cfg["model"], head_dim=128))
    got = [MAN.reducer("kernel_roofline").reduce(
        {"trace": t, "traced_units": 2, "manifest": MAN, "config": c,
         "peaks": peaks, "values": {}},
        MAN.metric_file("flash_head_dim_roofline")["args"])
        for c in (cfg, stated)]
    assert got[0] == got[1]
    kernel_s = sum(e.dur for e in t.devices[0].ops
                   if "tpu_custom_call" in e.name)
    assert got[0] == pytest.approx(
        100 * 2 * 30 * 9 * 2.0 * 128 * (4096 * 4097 / 2) * 24 / 197e12
        / kernel_s, rel=1e-9)
    assert "sc2-3b_train_1chip" in next(
        m for m in MAN.doc["per_layer"]
        if m["name"] == "flash_head_dim_roofline")["workloads"]


def test_the_cell_is_the_issues():
    w = MAN.workload(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (NAME, "train_steps", 1)
    listed = {m["name"] for m in MAN.metrics_for(CELL, "per_layer")}
    assert {"train_ssm_ms_step", "train_ssm_scan_ms_step", "train_moe_ms_step",
            "train_moe_experts_ms_step", "train_moe_dispatch_ms_step",
            "grouped_matmul_roofline", "train_rmsnorm_ms_step",
            "flash_head_dim_roofline",
            "train_mfu", "train_attn_ms_step", "train_unscoped_pct"} <= listed
    assert not {"train_ffn_ms_step", "train_norm_ms_step",
                "flash_roofline"} & listed
    assert {m["name"] for m in MAN.metrics_for(CELL, "end_to_end")} == \
        {"train_tokens_per_s", "setup_s"}
    tr = CFG["train"]
    assert (tr["seq_len"], tr["rows_per_chip"]) == (8192, 1)
    assert tr["dataset_rows_per_chip"] >= 160
