"""The ``command-a-plus-05-2026`` configuration and its cell
``command-a-plus_serve_backlog_longctx``: the file against the catalog's row
(every width equal, the three cuts listed and on their floors), the byte
counts, the ring's length, the new traffic mix under the host's replay, every
new metric file over a rehearsal's facts, and the rehearsal preset through the
shipped ``decode_backlog`` runner."""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import program_trace as pt
from perfbench import run as prun
from perfbench import serving, traffic_gen, weights
from perfbench import trace as ptrace
from perfbench.lint import cut_problems
from perfbench.manifest import Manifest
from perfbench.spans import Clock

pytestmark = pytest.mark.tier1

NAME = "command-a-plus-05-2026"
CELL = "command-a-plus_serve_backlog_longctx"
MAN = Manifest()
CFG = MAN.config(NAME)
TR = MAN.traffic("decode_backlog_longctx")
CUTS = {"num_hidden_layers": 4, "num_experts": 16, "vocab_size": 32768}

#: config.json of CohereLabs/command-a-plus-05-2026, the catalog's row
PUBLISHED = {
    "attention_bias": False, "expert_selection_fn": "sigmoid",
    "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
    "layer_switch": 4, "logit_scale": 1, "max_position_embeddings": 200000,
    "model_type": "cohere2_moe", "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 32, "num_key_value_heads": 8,
    "num_shared_experts": 4, "position_embedding_type": "rope_gptj",
    "prefix_dense_intermediate_size": 16384, "rope_theta": 50000,
    "rotary_pct": 1, "shared_expert_combination_strategy": "average",
    "sliding_window": 4096, "tie_word_embeddings": True,
    "use_gated_activation": True, "use_parallel_block": True,
    "use_qk_norm": False, "vocab_size": 262144}

#: the rate this file's windows stand on, tokens/s: the builder's median
#: (PERF.md section 6, PR 47)
RATE = 1177.0


def test_the_cut_is_sound_and_is_depth_experts_and_vocabulary():
    assert cut_problems(CFG) == []
    assert CFG["reduced"] == sorted(CUTS, key=list(CUTS).index)
    m, pub = CFG["model"], CFG["published"]
    assert (m["n_layers"], m["experts_held"], m["vocab"]) == (4, 16, 32768)
    # on their floors: one period and four layers, >= 8 experts, an eighth
    assert m["layer_kinds"] == "WWWG" and CFG["layer_pattern"] == dict(
        CFG["layer_pattern"], period=4, leading_dense=0, of="layer_types")
    assert pub["layer_types"][:4] == ["sliding_attention"] * 3 \
        + ["full_attention"] and pub["layer_types"] == pub["layer_types"][:4] * 8
    assert m["vocab"] * 8 == pub["vocab_size"]
    assert m["experts_held"] * m["ep_of"] == pub["num_experts"]
    for key in ("deployment", "assumed", "departures"):
        assert CFG[key], key
    assert "8 chips share each layer" in CFG["deployment"]
    assert {"layer_norm", "shared_expert_combination_strategy", "ffn_sum",
            "weights", "pool"} <= set(CFG["assumed"])
    assert any("vision" in d for d in CFG["departures"])
    entry = MAN.config_entry(NAME)
    assert entry["source"] == CFG["source"] \
        and entry["reduced"] == CFG["reduced"]
    assert CFG["source"].endswith(
        "CohereLabs/command-a-plus-05-2026/blob/main/config.json")


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_published_keys_to_the_digit(key):
    assert CFG["published"][key] == PUBLISHED[key]
    assert CFG[key] == CUTS.get(key, PUBLISHED[key])


def test_every_catalog_key_is_repeated_at_the_top_level():
    for key, value in CFG["published"].items():
        assert CFG[key] == CUTS.get(key, value), key


@pytest.mark.parametrize("field,key", sorted(CFG["published_as"].items()))
def test_every_field_is_the_published_one(field, key):
    if key in CFG["reduced"]:
        assert CFG["model"][field] == CUTS[key] < CFG["published"][key]
    else:
        assert CFG["model"][field] == CFG["published"][key]


def test_weight_tree_is_the_programs_at_the_published_widths():
    m = CFG["model"]
    model = MAN.program(CFG)(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, **m)
    want = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 128), jnp.int32))["params"],
        jax.random.PRNGKey(0))
    specs = MAN.weights(CFG).param_specs(m)
    got = jax.tree_util.tree_map(lambda s: s[0], specs,
                                 is_leaf=weights._is_spec)
    assert jax.tree_util.tree_map(lambda a: a.shape, want) == got
    # ISSUE 47's arithmetic: 4 x (344.4 + 16 x 50.33) M + 32,768 x 4,096
    assert weights.n_params(specs) == MAN.flops(CFG).n_params(m) + 5 * 4096
    assert round(weights.n_params(specs) / 1e9, 3) == 4.733
    assert model.state_shapes() == [{}] * 4 and model.serve_counters


def test_bytes_and_flops_against_a_hand_count():
    """ISSUE 47's arithmetic: 344.4 M a layer outside the routed experts,
    50.33 M an expert, 4 KB a token a layer, 9.47 GB of weights a tick."""
    flops, m = MAN.flops(CFG), CFG["model"]
    parts = flops.layer_params(m)
    assert parts == {"attention": 2 * 4096 * 16384 + 2 * 4096 * 1024,
                     "router": 4096 * 128, "experts": 16 * 3 * 4096 * 4096,
                     "shared": 3 * 4096 * 16384}
    outside = parts["attention"] + parts["router"] + parts["shared"]
    assert round(outside / 1e6, 1) == 344.5  # ISSUE 47: 344.4
    assert round(parts["experts"] / 16 / 1e6, 2) == 50.33
    assert flops.kv_bytes_per_token(m) == 4096
    tick = flops.tick_bytes(m, [2000] * 8 + [6000] * 16 + [12000] * 8)
    assert round(tick["weights"] / 1e9, 2) == 9.47
    assert tick["kv_full"] == 4096 * 208000
    assert tick["kv_window"] == 3 * 4096 * (8 * 2000 + 24 * 4096)
    assert 0.67 < 4 * parts["experts"] * 2 / tick["weights"] < 0.69  # 68%
    w, g = (flops.layer_flops_per_token(m, 8192, k) for k in "WG")
    assert w["attention"] < g["attention"] and w["experts"] == g["experts"] \
        == 6 * 4096 * 4096
    assert flops.train_flops_per_token(m, 8192) == 3 * (
        3 * sum(w.values()) + sum(g.values()) + 2 * 4096 * 32768)


def test_the_pool_and_the_rings_fill_the_chip_as_the_file_says():
    from chainermn_tpu.ops.decode_attention import ring_blocks

    sv, m = CFG["serve"], CFG["model"]
    assert sv == {"capacity": 32, "block_len": 128, "max_ctx": 14336,
                  "num_blocks": 3585, "prefill_chunk": 256,
                  "prefix_cache": False}
    assert sv["num_blocks"] == 32 * (sv["max_ctx"] // 128) + 1
    R = ring_blocks(m["window"], sv["prefill_chunk"], sv["block_len"])
    assert R == 34
    model = MAN.program(CFG)(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, **m)
    rings = model.ring_shapes(32, 128, 256)
    assert rings == [((32, 34, 128, 2048), jnp.bfloat16)] * 3 + [None]
    ring_b = 3 * 32 * 34 * 128 * 4096
    pool_b = 3585 * 128 * 4096
    weights_b = weights.n_params(MAN.weights(CFG).param_specs(m)) * 2
    assert round(ring_b / 1e9, 2) == 1.71 and round(pool_b / 1e9, 2) == 1.88
    assert round(weights_b / 1e9, 2) == 9.47
    assert 0.80 < (weights_b + ring_b + pool_b) / 16e9 < 0.85
    assert weights_b / 16e9 > 0.59  # the weights alone pass the floor


# ---------------------------------------------------------- the traffic mix
def _replay(**kw):
    return traffic_gen.replay_backlog(
        traffic_gen.backlog_lengths(TR), TR["slots"],
        CFG["serve"]["prefill_chunk"], **kw)


def test_the_mix_is_the_issues():
    w = MAN.workload(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == \
        (NAME, "decode_backlog_longctx", 1)
    assert TR["kind"] == "decode_backlog" and TR["slots"] == 32
    assert (TR["queue_sets"], TR["queue_left_min"], TR["order_salt"]) == \
        (7, 32, 100)
    assert TR["prompt"] == {"dist": "loguniform", "min": 2048, "max": 12288}
    assert TR["output"] == {"dist": "loguniform", "min": 512, "max": 2048}
    assert (TR["check_requests"], TR["check_pad"], TR["trace_ticks"]) == \
        (4, 14336, 40)
    lengths = traffic_gen.backlog_lengths(TR)
    assert len(lengths) == 256
    assert max(p + o for p, o in lengths) <= CFG["serve"]["max_ctx"]
    prompts = [p for p, _ in lengths]
    assert 5600 < np.mean(prompts) < 5800
    assert 0.60 < np.mean([p > 4096 for p in prompts]) < 0.63  # ISSUE: 61%
    assert 1090 < np.mean([o for _, o in lengths]) < 1120
    for s in (1, 2**31 + 5):  # the seed draws ids, never lengths or order
        reqs = traffic_gen.decode_backlog(TR, CFG["model"]["vocab"], s)
        assert [(len(r.prompt), r.max_new) for r in reqs] == lengths
        assert max(max(r.prompt) for r in reqs[:4]) < CFG["model"]["vocab"]


def test_the_queue_outlasts_twice_the_rate():
    fill, ticks = _replay()
    assert fill == 47  # 12,288 / 256 chunks a slot, less the last's tick
    assert sum(t.calls for t in ticks[:fill]) == 733
    queued = TR["slots"] * TR["queue_sets"]

    def left_after(tokens):
        done, admitted = 0.0, sum(t.admitted for t in ticks[:fill])
        for t in ticks[fill:]:
            if done >= tokens:
                break
            done += t.tokens
            admitted += t.admitted
        assert done >= tokens, "the queue ran out"
        return queued + TR["slots"] - admitted

    # ISSUE 47: at twice 1,600 tokens/s the queue must still hold 32
    assert left_after(2 * 1600 * MAN.doc["run_seconds"]) \
        >= TR["queue_left_min"] >= TR["slots"]
    assert left_after(RATE * MAN.doc["run_seconds"]) > queued / 2


def test_the_traced_ticks_stand_for_the_window():
    """``trace_from_tick`` / ``trace_ticks`` under the fixed schedule: the
    stretch holds riding chunks, calls of their own and plain ticks in about
    the whole window's proportions (the window: as many ticks as 45 s hold at
    :data:`RATE`), and lies inside a window a third slower."""
    fill, ticks = _replay(max_ticks=4000)
    per_tick = sum(t.tokens for t in ticks[fill:fill + 1200]) / 1200
    n = int(RATE * MAN.doc["run_seconds"] / per_tick)
    window = ticks[fill:fill + n]
    a = TR["trace_from_tick"]
    assert a + TR["trace_ticks"] < 0.66 * n
    traced = window[a:a + TR["trace_ticks"]]
    assert len(traced) == TR["trace_ticks"] >= 32

    def shares(ts):
        calls, rode = sum(t.calls for t in ts), sum(t.rode for t in ts)
        return (calls / len(ts), rode / calls,
                sum(t.calls == 0 for t in ts) / len(ts),
                sum(t.context for t in ts) / len(ts))

    got, want = shares(traced), shares(window)
    assert sum(t.calls - t.rode for t in traced) >= 2
    assert sum(t.rode for t in traced) >= 10
    assert sum(t.calls == 0 for t in traced) >= 5
    assert got[0] == pytest.approx(want[0], rel=0.20)
    assert abs(got[1] - want[1]) < 0.03
    assert abs(got[2] - want[2]) < 0.08
    assert got[3] == pytest.approx(want[3], rel=0.10)
    # the window's first quarter is plain decode ticks: nothing finishes
    # before the shortest output is out
    assert all(t.calls == 0 for t in window[:400])
    assert 6000 < want[3] / 32 < 6800 and 31.0 < per_tick <= 32.0


# ------------------------------------------------- the block's own modules
def _rehearsal_engine(seed=2**31 + 47):
    model, m, pdt, specs = serving.build_model(MAN, CFG, rehearse=True)
    params = weights.make_params(specs, seed, pdt)
    eng, sv = serving.build_engine(CFG, model, params, rehearse=True)
    return eng, m, sv, params


def test_every_scope_of_the_file_is_in_the_engines_programs():
    """The three programs at the rehearsal size, lowered: every scope the
    file lists is on an operation of one of them; the decode rows' read
    under ``attn.window`` (the window layers') and ``attn.paged`` (the full
    layer's), a chunk's under ``attn.window`` and ``attn.gathered``; the
    training names of the expert layer in all three."""
    eng, m, sv, _ = _rehearsal_engine()
    S, C, MB = sv["capacity"], sv["prefill_chunk"], eng.max_blocks
    i32 = jnp.int32
    rng, temp = eng._rng_temp()

    def text(fn, *args):
        fn = fn._fn if hasattr(fn, "_fn") else fn
        return fn.lower(*args).as_text(debug_info=True)

    step = text(eng._step, eng.params, eng.pools, jnp.zeros((S,), i32),
                jnp.zeros((S,), i32), jnp.zeros((S, MB), i32),
                jnp.zeros((S,), bool), rng, temp)
    mixed = text(eng._mixed, eng.params, eng.pools, jnp.zeros((S + C,), i32),
                 jnp.zeros((S + C + 2,), i32), jnp.zeros((S + 1, MB), i32),
                 jnp.zeros((S + C,), bool), rng, temp)
    prefill = text(eng._prefill, eng.params, None, eng.pools, None,
                   jnp.zeros((1, C), i32), np.int32(0),
                   jnp.zeros((1, MB), i32), np.int32(-1), eng.rng[0],
                   np.float32(0), np.int32(0))

    def has(txt, scope):
        return bool(pt.token_regex(re.escape(scope)).search(txt))

    for scope in CFG["scopes"]:
        assert has(step, scope) or has(mixed, scope) or has(prefill, scope), \
            scope
    moe = ("moe.route", "moe.dispatch", "moe.experts", "moe.shared",
           "moe.combine")
    for txt in (step, mixed, prefill):
        assert all(has(txt, s) for s in moe + ("attn.window", "kv_write"))
    assert has(step, "attn.paged") and not has(step, "attn.gathered")
    assert has(prefill, "attn.gathered") and not has(prefill, "attn.paged")
    assert has(mixed, "attn.paged") and has(mixed, "attn.gathered")
    # the program's vocabulary covers the file's: nothing reads as unscoped
    assert set(CFG["scopes"]) >= set(moe) | {"attn.window", "layer_norm"}


def test_reference_gradients_against_finite_differences():
    """``loss_and_grads`` is autodiff of the reference's own forward (it
    guards no cell: the model is served)."""
    m = dict(CFG["rehearse"]["model"], vocab=64)
    ref = MAN.reference(CFG)
    params = weights.make_params(MAN.weights(CFG).param_specs(m), 3,
                                 jnp.float32)
    rows = traffic_gen.markov_rows(2, 40, m["vocab"], 5)
    toks, targets = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])
    grads = {}
    loss = ref.loss_and_grads(params, toks, targets, m,
                              on_layer_grads=grads.__setitem__)
    assert set(grads) == set(params) and np.isfinite(loss)
    logits = ref.forward_logits(params, toks, m)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    assert loss == pytest.approx(float(jnp.mean(lse - picked)), rel=1e-5)

    def loss_at(p):
        return ref.loss_and_grads(p, toks, targets, m)

    for path in (("block_0", "kv", "kernel"), ("block_3", "q", "kernel"),
                 ("block_1", "experts_down"),
                 ("block_2", "shared_gate_up", "kernel"),
                 ("block_0", "proj", "kernel"), ("norm_f",)):
        leaf, g = params, grads
        for k in path:
            leaf, g = leaf[k], g[k]
        d = jax.random.normal(jax.random.PRNGKey(len(path)), leaf.shape)
        d = d / jnp.linalg.norm(d)
        eps = 1e-2

        def moved(sign):
            out = jax.tree_util.tree_map(lambda a: a, params)
            node = out
            for k in path[:-1]:
                node[k] = dict(node[k])
                node = node[k]
            node[path[-1]] = leaf + sign * eps * d
            return out

        numeric = (loss_at(moved(+1)) - loss_at(moved(-1))) / (2 * eps)
        assert numeric == pytest.approx(float(jnp.sum(g * d)), rel=0.05,
                                        abs=2e-4), path


# -------------------------------------------------------------- the metrics
NEW_METRICS = ("serve_moe_ms_tick", "serve_moe_experts_ms_tick",
               "serve_attn_window_ms_tick", "serve_ring_hbm_gb",
               "serve_moe_experts_touched", "serve_grouped_matmul_roofline",
               "paged_window_roofline")


def test_the_cell_lists_its_metrics_and_no_roofline_of_another_models_bytes():
    per_layer = {m["name"]: m for m in MAN.doc["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "serve_tokens_per_s"
        assert MAN.metric_file(name)["unit"] == per_layer[name]["unit"]
    # their needs count the contexts times n_layers: not this model's bytes
    for name in ("paged_roofline", "paged_head_dim_roofline"):
        assert CELL not in per_layer[name]["workloads"]
    listed = {m["name"] for m in MAN.metrics_for(CELL, "per_layer")}
    old = {m["name"] for m in MAN.metrics_for("gpt2-xl_serve_backlog",
                                              "per_layer")}
    assert listed - old == set(NEW_METRICS)
    assert old - listed == {"paged_roofline"}
    assert [m["name"] for m in MAN.metrics_for(CELL, "end_to_end")] == \
        ["serve_tokens_per_s", "setup_s"]


@pytest.fixture(scope="module")
def rehearsal():
    """The cell at its rehearsal size, driven as its runner drives it, with
    the program's own unit ledger; the device's side is made up: one tick's
    events under the scopes and kernel names the programs carry, 3 ticks of
    them."""
    from chainermn_tpu import observability as obs

    tr = dict(TR, **TR["rehearse"])
    eng, m, sv, _ = _rehearsal_engine()
    clock = Clock()
    reqs = traffic_gen.decode_backlog(tr, m["vocab"], 2**31 + 48)
    sched, rec = serving.new_scheduler(eng, clock, reqs)
    for r in reqs:
        serving.submit(sched, r, 0.0)
    while sched.pending:
        assert sched.tick()
    ledger = obs.unit_ledger("serve_tick")
    assert ledger is sched._units
    units = ledger.units()
    first = 12  # ticks with every slot decoding
    traced = [u.ordinal for u in units][first:first + tr["trace_ticks"]]
    spans = [pt.Span("pb:window", 0.0, 100.0)]
    ops = []
    L = "jit(step_impl)/HybridLM/block_0/block_0._parallel/"
    per_tick = [("moe.route/dot_general", 0.001),
                ("moe.dispatch/gather", 0.0005),
                ("moe.shared/dot_general", 0.002),
                ("moe.combine/scatter-add", 0.0005)]
    for k, ordinal in enumerate(traced):
        t0 = 1.0 + k
        spans.append(pt.Span("cmn_serve_tick", t0, t0 + 0.5,
                             {"tick": ordinal}))
        t = t0
        for scope, dur in per_tick:
            ops.append(pt.DeviceEvent("%fusion.1 = f32[8] fusion()", t,
                                      t + dur, L + scope))
            t += dur
        for i in range(2):  # [gate | up], then down
            ops.append(pt.DeviceEvent(
                f"%grouped_matmul.{i} = bf16[8] custom-call()", t, t + 0.003,
                L + "moe.experts/grouped_matmul/pallas_call"))
            t += 0.003
        ops.append(pt.DeviceEvent(
            "%paged_decode_window.3 = bf16[8] custom-call()", t, t + 0.002,
            L + "attn.window/paged_decode_window/pallas_call"))
        ops.append(pt.DeviceEvent(
            "%paged_decode.4 = bf16[8] custom-call()", t + 0.002, t + 0.003,
            L + "attn.paged/paged_decode/pallas_call"))
    pt._nest(spans)
    prog = pt.ProgramTrace("made-up", spans, {0: ops})
    trace = ptrace.Trace(
        {0: ptrace.DeviceTrace([ptrace.Event(e.name, e.start, e.end)
                                for e in ops], [])},
        [ptrace.Event("pb:window", 0.0, 100.0)])
    from perfbench import device

    facts = {"program_trace": prog, "trace": trace,
             "traffic": dict(tr, trace_from_tick=first),
             "traced_units": len(traced), "manifest": MAN,
             "config": dict(CFG, model=m, serve=sv,
                            dtype={"compute": "float32"}), "values": {},
             "peaks": device.peaks("TPU v5 lite"),
             "unit_ledgers": {"serve_tick": ledger}}
    return {"facts": facts, "units": [u for u in units
                                      if u.ordinal in traced],
            "model": m, "eng": eng, "sv": sv}


def _reduce(name, facts):
    spec = MAN.metric_file(name)
    return MAN.reducer(spec["reducer"]).reduce(facts, spec["args"])


def test_every_new_metric_reads_a_number_from_the_rehearsals_facts(
        rehearsal, capsys):
    facts, units = rehearsal["facts"], rehearsal["units"]
    got = {name: _reduce(name, facts) for name in NEW_METRICS
           if name != "serve_ring_hbm_gb"}
    assert all(v is not None and np.isfinite(v) for v in got.values()), got
    assert got["serve_moe_ms_tick"] == pytest.approx(10.0)
    assert got["serve_moe_experts_ms_tick"] == pytest.approx(6.0)
    assert got["serve_attn_window_ms_tick"] == pytest.approx(2.0)
    assert 0 < got["serve_grouped_matmul_roofline"] < 100
    assert 0 < got["paged_window_roofline"] < 100
    # the counter: held experts that drew a row, a layer a program run
    touched = sum(u.counts["cmn_engine_readback.moe_experts_touched"]
                  for u in units)
    layers = sum(u.counts["cmn_engine_readback.moe_layers"] for u in units)
    assert layers % 4 == 0 and layers >= 4 * len(units)
    assert got["serve_moe_experts_touched"] == pytest.approx(touched / layers)
    assert 1.0 <= got["serve_moe_experts_touched"] <= 4.0
    # the gauge: what the memory monitor published of the engine's rings
    from chainermn_tpu.observability import memory, metrics

    eng = rehearsal["eng"]
    memory.MemoryMonitor(registry=metrics.registry()).sample(
        memory.kv_pool_sample(eng))
    assert _reduce("serve_ring_hbm_gb", facts) == pytest.approx(
        eng.pool.ring_bytes / 1e9)
    assert eng.pool.ring_bytes == 3 * 4 * 5 * 8 * (2 * 2 * 16) * 4
    capsys.readouterr()


def test_the_two_needs_by_a_hand_count(rehearsal, capsys):
    facts, units, m = (rehearsal["facts"], rehearsal["units"],
                       rehearsal["model"])
    # the window layers' read: the resident ring blocks, not the contexts
    blocks = sum(u.counts["cmn_serve_decode.ring_blocks_resident"]
                 for u in units)
    live = sum(u.counts["cmn_serve_decode.live"] for u in units)
    assert 0 < live <= blocks <= 4 * live  # 1 .. window / block + 1 a slot
    need = MAN.need("paged_window")(facts, 999)  # not the events' count
    positions = blocks * 3 * 8
    assert need == {"flops": 4.0 * positions * 8 * 16,
                    "bytes": 2.0 * positions * 2 * 16 * 4}
    got = _reduce("paged_window_roofline", facts)
    assert got == pytest.approx(
        100 * max(need["bytes"] / 819e9, need["flops"] / 197e12)
        / (0.002 * len(units)))
    # the expert kernels: every held expert's matrices a launch, + the rows
    pairs = sum(u.counts["cmn_engine_readback.moe_pairs_held"] for u in units)
    calls = 2 * len(units)
    need = MAN.need("grouped_matmul_decode")(facts, calls)
    D, F, E = m["d_model"], m["d_expert"], m["experts_held"]
    assert need == {"flops": 2.0 * pairs * 3 * D * F,
                    "bytes": 4 * (calls * E * 1.5 * D * F
                                  + pairs * (2 * D + 3 * F))}
    # at the published shapes a plain tick's 8 launches stream 6.44 GB
    empty = {"serve_tick": type("L", (), {"units": lambda self: []})()}
    full = dict(facts, config=CFG, unit_ledgers=empty)
    assert round(MAN.need("grouped_matmul_decode")(full, 8)["bytes"] / 1e9,
                 2) == 6.44
    # a program without the counts needs nothing of the window kernel
    assert MAN.need("paged_window")(dict(facts, unit_ledgers=empty), 1) == \
        {"flops": 0.0, "bytes": 0.0}
    capsys.readouterr()


# ------------------------------------------------------------ the rehearsal
def test_rehearsal_goes_through_the_shipped_runner(capsys):
    rc = prun.main(["--workload", CELL, "--seed", str(2**31 + 47),
                    "--seconds", "0.05", "--rehearse"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    last = lines[-1]
    assert rc == 0 and last["rehearsal"] is True and last["correct"] is True
    assert last["attempted"] > 0 and last["failed"] == 0
    assert set(last["compared"]) == {"queue_short", "served_gap_mean",
                                     "served_gap_widest"}
    info = next(x["info"] for x in lines if "info" in x)
    assert info["schedule"]["off"] == 0
    assert info["schedule"]["fill"] == info["schedule"]["fill_replay"]


def test_fp8_control_fails_the_rehearsal_limits_and_float32_does_not():
    from perfbench.checks import serve as check

    m = CFG["rehearse"]["model"]
    ref = MAN.reference(CFG)
    params = weights.make_params(MAN.weights(CFG).param_specs(m), 9,
                                 jnp.float32)
    reqs = [traffic_gen.Req(i, [1 + (7 * i + 3 * j) % 500 for j in range(n)],
                            12) for i, n in enumerate((20, 45))]
    tokens = {}
    for r in reqs:
        text = list(r.prompt)
        for _ in range(r.max_new):
            row = np.zeros((1, 64), np.int32)
            row[0, :len(text)] = text
            logits = ref.forward_logits(params, jnp.asarray(row), m)
            text.append(int(np.argmax(np.asarray(logits[0, len(text) - 1]))))
        tokens[r.id] = text[len(r.prompt):]
    limits = CFG["rehearse"]["check"]["serve"]
    sound = check.served_gaps(ref, m, params, reqs, tokens, 64)
    low = check.served_gaps(ref, m, params, reqs, tokens, 64, quant="fp8")
    assert check.judge(sound, limits)[0]
    assert not check.judge(low, limits)[0]
    assert low["served_gap_mean"] > limits["served_gap_mean"]


# ------------------------------------------------ recorded on the chip
FIXTURE = __import__("os").path.join(
    __import__("os").path.dirname(pt.__file__), "fixtures",
    "serve_longctx_2ticks_program.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded():
    """The first two traced ticks (window ticks 562 and 563: a riding chunk
    beside a call of its own, twice) of my chip run, PR 47, seed 2147491617,
    cut by ``perfbench.cut_program_fixture`` on the chip."""
    t = pt.load(FIXTURE)
    if t is None:
        pytest.skip("no xplane_pb2 in this installation")
    from perfbench import device

    facts = {"program_trace": t, "trace": ptrace.load(FIXTURE),
             "traced_units": 2, "manifest": MAN, "config": CFG,
             "traffic": TR, "values": {},
             "peaks": device.peaks("TPU v5 lite")}
    return t, facts


def test_the_recorded_ticks_hold_both_kinds_of_read(recorded):
    t, _ = recorded
    table = pt.by_scope(t, 2, tuple(CFG["scopes"]))
    assert table["sum_ms"] == pytest.approx(table["busy_ms"], rel=0.02)
    for scope in ("moe.experts", "moe.shared", "moe.route", "moe.dispatch",
                  "moe.combine", "attn.window", "attn.paged",
                  "attn.gathered", "kv_write", "layer_norm", "head"):
        assert table["rows"][scope]["all"] > 0, scope
    assert table["rows"]["unscoped"]["all"] < 0.1 * table["busy_ms"]
    names = " ".join(table["ops"])
    for kernel in ("paged_decode_window:mosaic:", "paged_decode:mosaic:",
                   "grouped_matmul:mosaic:"):
        assert kernel in names, kernel
    # decode rows of BOTH kinds of layer ran a kernel, under their scopes
    ops = [e for evs in t.devices.values() for e in evs]
    window = [e for e in ops if e.name.startswith("%paged_decode_window")]
    full = [e for e in ops if re.match(r"%paged_decode(\.\d+)? ", e.name)]
    assert len(window) == 3 * len(full) == 6
    assert all("attn.window" in e.scope for e in window)
    assert all("attn.paged" in e.scope for e in full)
    # the host's side: the counts the program attached
    decodes = t.named("cmn_serve_decode")
    assert len(decodes) == 2
    for s in decodes:
        assert s.stats["live"] == 30 and 0 < s.stats["chunk_rows"] <= 256
        assert s.stats["table_width"] == 112
        assert s.stats["live"] <= s.stats["ring_blocks_resident"] <= 33 * 30
        assert s.stats["kv_blocks_resident"] > s.stats["ring_blocks_resident"]
    reads = [s for s in t.named("cmn_engine_readback")
             if "moe_layers" in s.stats]
    assert len(reads) == 2 and all(s.stats["moe_layers"] == 4 for s in reads)
    assert all(0 < s.stats["moe_experts_touched"] <= 64
               and s.stats["moe_pairs_dropped"] == 0 for s in reads)
    assert {s.stats.get("program") for s in t.named("cmn_engine_dispatch")} \
        == {"decode_step", "prefill"}


def test_every_new_reducer_reads_the_recorded_ticks(recorded, capsys):
    """The units are the trace's own (a fixture has no ledger): the scope
    times, the two rooflines under 100% and the touched experts, from the
    program's own events and counts."""
    from perfbench.reducers import unit_ledger

    t, facts = recorded
    units = unit_ledger.units_of_trace(t, "cmn_serve_tick")
    for u, span in zip(units, sorted(t.named("cmn_serve_tick"),
                                     key=lambda s: s.start)):
        u.ordinal = int(span.stats["tick"])  # as the program's ledger has it
    ledger = type("Ledger", (), {"units": lambda self: units})()
    facts = dict(facts, unit_ledgers={"serve_tick": ledger},
                 traffic=dict(TR, trace_from_tick=0))
    got = {name: _reduce(name, facts) for name in NEW_METRICS
           if name != "serve_ring_hbm_gb"}
    assert all(v is not None and np.isfinite(v) for v in got.values()), got
    # (both ticks also hold a chunk's call of its own: two passes a tick)
    assert 20 < got["serve_moe_ms_tick"] < 40
    assert 12 < got["serve_moe_experts_ms_tick"] < got["serve_moe_ms_tick"]
    assert 4 < got["serve_attn_window_ms_tick"] < 12
    assert 10 <= got["serve_moe_experts_touched"] <= 16
    assert 60 < got["serve_grouped_matmul_roofline"] < 100
    assert 30 < got["paged_window_roofline"] < 100
    capsys.readouterr()
