"""The ``Falcon-H1-34B-Instruct`` configuration and its cell
``falcon-h1-34b_serve_backlog64``: the cut held to the published file, the
block's modules (weight tree, reference, byte and FLOP counts, scopes), the
new traffic mix under the host's replay, every new metric file over a
rehearsal's facts, and the rehearsal preset through the shipped
``decode_backlog`` runner."""

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

NAME = "Falcon-H1-34B-Instruct"
CELL = "falcon-h1-34b_serve_backlog64"
MAN = Manifest()
CFG = MAN.config(NAME)
TR = MAN.traffic("decode_backlog64")

#: config.json of tiiuae/Falcon-H1-34B-Instruct, as ISSUE 44 repeats it
PUBLISHED = {
    "hidden_size": 5120, "num_attention_heads": 20, "num_key_value_heads": 4,
    "head_dim": 128, "intermediate_size": 21504, "mamba_d_ssm": 4096,
    "mamba_n_heads": 32, "mamba_d_head": 128, "mamba_d_state": 256,
    "mamba_n_groups": 2, "mamba_d_conv": 4, "mamba_chunk_size": 128,
    "vocab_size": 261120, "rope_theta": 100000000000,
    "num_hidden_layers": 72, "rms_norm_eps": 1e-05,
    "tie_word_embeddings": False, "attn_layer_indices": None,
    "mamba_rms_norm": True, "mamba_norm_before_gate": False,
    "mamba_conv_bias": True, "model_type": "falcon_h1",
    "embedding_multiplier": 5.656854249492381,
    "lm_head_multiplier": 0.0078125, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "attention_in_multiplier": 1,
    "key_multiplier": 0.011048543456039804,
    "attention_out_multiplier": 0.0375,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284]}

#: the rate the cell's limits and this file's windows stand on: the median
#: of the builder's two sets of six (PERF.md section 6, PR 44), tokens/s
RATE = 1845.0


def test_the_cut_is_sound_and_is_depth_alone():
    assert cut_problems(CFG) == []
    assert CFG["reduced"] == ["num_hidden_layers"]
    m, pub = CFG["model"], CFG["published"]
    assert (m["n_layers"], pub["num_hidden_layers"]) == (6, 72)
    assert m["layer_kinds"] == "F" * 6
    assert CFG["layer_pattern"]["period"] == 1 \
        and CFG["layer_pattern"]["leading_dense"] == 0
    for key in ("deployment", "assumed", "departures"):
        assert CFG[key], key
    assert "72" in CFG["deployment"] and "34%" in CFG["deployment"] \
        and "host" in CFG["deployment"]
    assert {"state", "weights", "pool"} <= set(CFG["assumed"])
    entry = MAN.config_entry(NAME)
    assert entry["source"] == CFG["source"] and entry["reduced"] == CFG["reduced"]
    assert CFG["source"].endswith("Falcon-H1-34B-Instruct/blob/main/config.json")


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_the_published_keys_to_the_digit(key):
    """Under ``published`` as the source has it, and at the file's top level
    as it runs (the depth alone differs)."""
    assert CFG["published"][key] == PUBLISHED[key]
    assert CFG[key] == (6 if key == "num_hidden_layers" else PUBLISHED[key])


def test_every_catalog_key_is_repeated_at_the_top_level():
    for key, value in CFG["published"].items():
        assert CFG[key] == (6 if key == "num_hidden_layers" else value), key


@pytest.mark.parametrize("field,key", sorted(CFG["published_as"].items()))
def test_every_field_is_the_published_one(field, key):
    if key in CFG["reduced"]:
        assert CFG["model"][field] < CFG["published"][key]
    else:
        assert CFG["model"][field] == CFG["published"][key]
        assert CFG["rehearse"]["model"][field] == CFG["published"][key] \
            or not isinstance(CFG["published"][key], (float, list))


def test_fourteen_multipliers_and_the_inner_width():
    m = CFG["model"]
    mult = [k for k in m if "multiplier" in k]
    assert sum(len(m[k]) if isinstance(m[k], list) else 1 for k in mult) == 14
    assert all(k in CFG["published_as"] for k in mult)
    assert m["ssm_heads"] * m["ssm_head_dim"] == CFG["published"]["mamba_d_ssm"]
    assert m["n_heads"] * m["head_dim"] == 2560 != m["d_model"]


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
    # ISSUE 44's arithmetic: 6 x 430.1 M and 2 x 261,120 x 5,120
    assert round(weights.n_params(specs) / 1e6) == round(
        (6 * 430.14 + 2 * 1336.93))
    assert model.state_shapes()[0] == {
        "ssm": ((32, 128, 256), jnp.float32),
        "conv": ((3, 4096 + 2 * 512), jnp.bfloat16)}


def test_bytes_and_flops_against_a_hand_count():
    """ISSUE 44's arithmetic: a layer is 430.1 M parameters, a slot 25.2 MB
    of state, a token 12 KB of keys and values, a tick 7.84 GB of weights."""
    flops, m = MAN.flops(CFG), CFG["model"]
    parts = flops.layer_params(m)
    assert parts == {"in_proj": 5120 * 9248, "out_proj": 4096 * 5120,
                     "attention": 5120 * (2560 + 1024) + 2560 * 5120,
                     "ffn": 3 * 5120 * 21504}
    assert round(sum(parts.values()) / 1e6, 1) == 430.1
    state = flops.state_bytes_per_slot(m)
    assert state == {"ssm": 6 * 32 * 128 * 256 * 4, "conv": 6 * 3 * 5120 * 2}
    assert round(state["ssm"] / 1e6, 1) == 25.2 and state["conv"] == 184320
    assert flops.kv_bytes_per_token(m) == 6 * 2 * 4 * 128 * 2 == 12288
    tick = flops.tick_bytes(m, live=63, context_tokens=27000)
    assert round(tick["weights"] / 1e9, 1) == 7.8  # ISSUE 44: 7.84
    assert round(tick["state"] / 1e9, 1) == 3.2
    assert 0.28 < tick["state"] / sum(tick.values()) < 0.30  # "a third"
    per = flops.layer_flops_per_token(m, 1024)
    assert per["ffn"] == 6 * 5120 * 21504 and per["head"] == 2 * 5120 * 261120
    layer = per["mamba"] + per["attention"] + per["ffn"]
    assert flops.train_flops_per_token(m, 1024) == 3 * (6 * layer + per["head"])
    assert 0.75 < per["ffn"] / layer < 0.80  # ISSUE 44: ~80% one SwiGLU


def test_the_pool_fills_the_chip_as_the_file_says():
    sv, m = CFG["serve"], CFG["model"]
    assert sv == {"capacity": 64, "block_len": 16, "max_ctx": 1024,
                  "num_blocks": 4097, "prefill_chunk": 64,
                  "prefix_cache": False}
    flops = MAN.flops(CFG)
    kv = sv["num_blocks"] * sv["block_len"] * flops.kv_bytes_per_token(m)
    state = sv["capacity"] * sum(flops.state_bytes_per_slot(m).values())
    weights_b = weights.n_params(MAN.weights(CFG).param_specs(m)) * 2
    assert round(kv / 1e9, 2) == 0.81 and round(state / 1e9, 2) == 1.62
    assert round(weights_b / 1e9, 2) == 10.51
    assert (weights_b + kv + state) / 16e9 > 0.8
    assert sv["prefill_chunk"] <= m["ssm_chunk"]  # a chunk is one scan chunk


# ---------------------------------------------------------- the traffic mix
def _replay(**kw):
    return traffic_gen.replay_backlog(
        traffic_gen.backlog_lengths(TR), TR["slots"],
        CFG["serve"]["prefill_chunk"], **kw)


def test_the_mix_is_the_issues():
    w = MAN.workload(CELL)
    assert (w["config"], w["traffic"], w["chips"]) == \
        (NAME, "decode_backlog64", 1)
    assert TR["kind"] == "decode_backlog" and TR["slots"] == 64
    assert TR["queue_sets"] >= 23 and TR["queue_left_min"] == 64
    assert TR["order_salt"] == 100
    for side in ("prompt", "output"):
        assert TR[side] == {"dist": "loguniform", "min": 128, "max": 512}
    assert (TR["check_requests"], TR["check_pad"], TR["trace_ticks"]) == \
        (4, 1024, 40)
    lengths = traffic_gen.backlog_lengths(TR)
    assert len(lengths) == 64 * (1 + TR["queue_sets"])
    assert max(p + o for p, o in lengths) <= CFG["serve"]["max_ctx"]
    for s in (1, 2**31 + 5):  # the seed draws ids, never lengths or order
        reqs = traffic_gen.decode_backlog(TR, CFG["model"]["vocab"], s)
        assert [(len(r.prompt), r.max_new) for r in reqs] == lengths
        assert max(max(r.prompt) for r in reqs[:8]) < CFG["model"]["vocab"]


def test_the_queue_outlasts_twice_the_rate():
    """At twice the rate the finished change sustains (:data:`RATE`) the
    window still closes with ``queue_left_min`` requests waiting, and at the
    rate itself with more than half the queue."""
    fill, ticks = _replay()
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

    budget = RATE * MAN.doc["run_seconds"]
    assert left_after(2 * budget) >= TR["queue_left_min"] >= TR["slots"]
    assert left_after(budget) > queued / 2


def test_the_traced_ticks_stand_for_the_window():
    """``trace_from_tick`` / ``trace_ticks`` under the fixed schedule: the
    stretch holds riding chunks, calls of their own and plain ticks in about
    the whole window's proportions (the window: as many ticks as 45 s hold at
    :data:`RATE`), and lies inside a window a third slower."""
    fill, ticks = _replay(max_ticks=3000)
    per_tick = sum(t.tokens for t in ticks[fill:fill + 1500]) / 1500
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
    assert got[0] == pytest.approx(want[0], rel=0.05)
    assert abs(got[1] - want[1]) < 0.02
    assert abs(got[2] - want[2]) < 0.05
    assert got[3] == pytest.approx(want[3], rel=0.10)
    # ISSUE 44: more ticks with two prefilling slots than gpt2-xl's third of
    # the chunks that cannot ride
    assert 0.55 < want[1] < 0.70 and 63.0 < per_tick < 63.5


# ------------------------------------------------- the block's own modules
def _rehearsal_engine(seed=2**31 + 44):
    model, m, pdt, specs = serving.build_model(MAN, CFG, rehearse=True)
    params = weights.make_params(specs, seed, pdt)
    eng, sv = serving.build_engine(CFG, model, params, rehearse=True)
    return eng, m, sv, params


def test_every_scope_of_the_file_is_in_the_engines_programs():
    """The three programs at the rehearsal size, lowered: every scope the
    file lists is on an operation of one of them, the decode rows' read
    under ``attn.paged`` and their recurrence under ``ssm.step``, a
    chunk's under ``attn.gathered`` and ``ssm.scan``."""
    eng, m, sv, _ = _rehearsal_engine()
    S, C, MB = sv["capacity"], sv["prefill_chunk"], eng.max_blocks
    i32 = jnp.int32
    rng, temp = eng._rng_temp()

    def text(fn, *args):
        return fn.lower(*args).as_text(debug_info=True)

    step = text(eng._step._fn if hasattr(eng._step, "_fn") else eng._step,
                eng.params, eng.pools, jnp.zeros((S,), i32),
                jnp.zeros((S,), i32), jnp.zeros((S, MB), i32),
                jnp.zeros((S,), bool), rng, temp)
    mixed = text(eng._mixed._fn if hasattr(eng._mixed, "_fn") else eng._mixed,
                 eng.params, eng.pools, jnp.zeros((S + C,), i32),
                 jnp.zeros((S + C + 2,), i32), jnp.zeros((S + 1, MB), i32),
                 jnp.zeros((S + C,), bool), rng, temp)
    prefill = text(
        eng._prefill._fn if hasattr(eng._prefill, "_fn") else eng._prefill,
        eng.params, None, eng.pools, None, jnp.zeros((1, C), i32),
        np.int32(0), jnp.zeros((1, MB), i32), np.int32(-1), eng.rng[0],
        np.float32(0), np.int32(0))

    def has(txt, scope):
        return bool(pt.token_regex(re.escape(scope)).search(txt))

    for scope in CFG["scopes"]:
        assert has(step, scope) or has(mixed, scope) or has(prefill, scope), \
            scope
    assert has(step, "attn.paged") and has(step, "ssm.step")
    assert not has(step, "ssm.scan") and not has(step, "attn.gathered")
    assert has(prefill, "ssm.scan") and has(prefill, "attn.gathered")
    assert not has(prefill, "ssm.step") and not has(prefill, "attn.paged")
    for scope in ("ssm.step", "ssm.scan", "attn.paged", "attn.gathered"):
        assert has(mixed, scope), scope


def test_reference_gradients_against_finite_differences():
    """``loss_and_grads`` is autodiff of the reference's own forward (it
    guards no cell: the model is served): directional derivatives of a few
    leaves against central differences, float64-free at a size of a test."""
    m = dict(CFG["rehearse"]["model"], vocab=64)
    ref = MAN.reference(CFG)
    params = weights.make_params(MAN.weights(CFG).param_specs(m), 3,
                                 jnp.float32)
    rows = traffic_gen.markov_rows(2, 16, m["vocab"], 5)
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

    for path in (("block_0", "A_log"), ("block_1", "in_proj", "kernel"),
                 ("block_0", "kv", "kernel"), ("block_1", "down", "kernel"),
                 ("lm_head", "kernel"), ("block_0", "dt_bias")):
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
NEW_METRICS = ("serve_ssm_ms_tick", "serve_ssm_step_ms_tick",
               "serve_ssm_scan_ms_tick", "serve_ffn_ms_tick",
               "serve_head_ms_tick", "serve_state_hbm_gb",
               "ssm_step_roofline", "paged_head_dim_roofline")


def test_the_cell_lists_its_metrics_and_not_the_twice_counted_roofline():
    per_layer = {m["name"]: m for m in MAN.doc["per_layer"]}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "serve_tokens_per_s"
        assert MAN.metric_file(name)["unit"] == per_layer[name]["unit"]
    assert CELL not in per_layer["paged_roofline"]["workloads"]
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
    events under the scopes the programs carry (the test above), 40 ticks
    of them."""
    from chainermn_tpu import observability as obs

    tr = dict(TR, **TR["rehearse"])
    eng, m, sv, _ = _rehearsal_engine()
    clock = Clock()
    reqs = traffic_gen.decode_backlog(tr, m["vocab"], 2**31 + 45)
    sched, rec = serving.new_scheduler(eng, clock, reqs)
    for r in reqs:
        serving.submit(sched, r, 0.0)
    while sched.pending:
        assert sched.tick()
    ledger = obs.unit_ledger("serve_tick")
    assert ledger is sched._units
    units = ledger.units()
    first = tr["trace_from_tick"]
    traced = [u.ordinal for u in units][first:first + tr["trace_ticks"]]
    spans = [pt.Span("pb:window", 0.0, 100.0)]
    ops = []
    L = "jit(step_impl)/HybridLM/block_0/block_0._falcon/"
    per_tick = [("ssm.step/mul", 0.004), ("ssm.scan/dot_general", 0.001),
                ("ssm.in_proj/dot_general", 0.002), ("ffn/dot_general", 0.008)]
    for k, ordinal in enumerate(traced):
        t0 = 1.0 + k
        spans.append(pt.Span("cmn_serve_tick", t0, t0 + 0.5,
                             {"tick": ordinal}))
        t = t0
        for scope, dur in per_tick:
            ops.append(pt.DeviceEvent("%fusion.1 = f32[8] fusion()", t,
                                      t + dur, L + "block_0._mamba/" + scope))
            t += dur
        ops.append(pt.DeviceEvent(
            "%paged_decode.3 = bf16[8] custom-call()", t, t + 0.0005,
            L + "attn.paged/paged_decode/pallas_call"))
        ops.append(pt.DeviceEvent("%fusion.9 = f32[8] fusion()", t + 0.001,
                                  t + 0.004, "jit(mixed_impl)/head/dot_general"))
    pt._nest(spans)
    prog = pt.ProgramTrace("made-up", spans, {0: ops})
    trace = ptrace.Trace(
        {0: ptrace.DeviceTrace([ptrace.Event(e.name, e.start, e.end)
                                for e in ops], [])},
        [ptrace.Event("pb:window", 0.0, 100.0)])
    from perfbench import device

    facts = {"program_trace": prog, "trace": trace, "traffic": tr,
             "traced_units": len(traced), "manifest": MAN,
             "config": dict(CFG, model=m), "values": {},
             "peaks": device.peaks("TPU v5 lite"),
             "traced_context_tokens": float(sum(
                 rec.context_per_step[:len(traced)])),
             "unit_ledgers": {"serve_tick": ledger}}
    return {"facts": facts, "units": [u for u in units
                                      if u.ordinal in traced],
            "model": m, "eng": eng, "sched": sched}


def _reduce(name, facts):
    spec = MAN.metric_file(name)
    return MAN.reducer(spec["reducer"]).reduce(facts, spec["args"])


def test_every_new_metric_reads_a_number_from_the_rehearsals_facts(
        rehearsal, capsys):
    facts = rehearsal["facts"]
    got = {name: _reduce(name, facts) for name in NEW_METRICS
           if name != "serve_state_hbm_gb"}
    assert all(v is not None and np.isfinite(v) for v in got.values()), got
    assert got["serve_ssm_step_ms_tick"] == pytest.approx(4.0)
    assert got["serve_ssm_scan_ms_tick"] == pytest.approx(1.0)
    assert got["serve_ssm_ms_tick"] == pytest.approx(7.0)
    assert got["serve_ffn_ms_tick"] == pytest.approx(8.0)
    assert got["serve_head_ms_tick"] == pytest.approx(3.0)
    assert 0 < got["ssm_step_roofline"] < 100
    assert 0 < got["paged_head_dim_roofline"] < 100
    # the gauge: what the memory monitor published of the engine's pool
    from chainermn_tpu.observability import memory, metrics

    eng = rehearsal["eng"]
    memory.MemoryMonitor(registry=metrics.registry()).sample(
        memory.kv_pool_sample(eng))
    assert _reduce("serve_state_hbm_gb", facts) == pytest.approx(
        eng.pool.state_bytes / 1e9)
    assert eng.pool.state_bytes == 4 * 2 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
    capsys.readouterr()


def test_ssm_state_step_need_by_a_hand_count(rehearsal, capsys):
    """Rows stepped = ``state_rows`` less the riding chunks, over the traced
    ticks; each a float32 ``(heads, head_dim, state)`` array a layer, read
    and written."""
    facts, units, m = (rehearsal["facts"], rehearsal["units"],
                       rehearsal["model"])
    rows = sum(u.counts.get("cmn_serve_decode.live", 0) for u in units)
    assert rows > 0
    assert rows == sum(u.counts["cmn_serve_decode.state_rows"]
                       - u.counts.get("cmn_serve_prefill.rode", 0)
                       for u in units if "cmn_serve_decode.state_rows"
                       in u.counts)
    need = MAN.need("ssm_state_step")(facts, 123)  # not the events' count
    elements = rows * 2 * 4 * 16 * 16
    assert need == {"flops": 6.0 * elements, "bytes": 8.0 * elements}
    got = _reduce("ssm_step_roofline", facts)
    step_s = 0.004 * len(units)
    assert got == pytest.approx(100 * (need["bytes"] / 819e9) / step_s)
    # at the published shapes: 63 rows of a tick are 3.2 GB, 3.9 ms
    full = dict(facts, config=CFG)
    per_row = MAN.need("ssm_state_step")(full, 1)["bytes"] / rows
    assert round(63 * per_row / 1e9, 2) == 3.17
    # a program without the count (or without a ledger) needs nothing
    assert MAN.need("ssm_state_step")(
        dict(facts, unit_ledgers={"serve_tick": type(
            "L", (), {"units": lambda self: []})()}), 1) == \
        {"flops": 0.0, "bytes": 0.0}
    capsys.readouterr()


def test_the_paged_need_reads_the_models_head_dim(rehearsal):
    """20 / 4 heads of 128 under a ``d_model`` of 5120: the shipped need
    (``d_model // n_heads`` = 256) would count twice the bytes."""
    facts = dict(rehearsal["facts"], config=CFG,
                 traced_context_tokens=1_000_000.0, traced_units=40,
                 traffic=TR)
    own = MAN.need("paged_decode_head_dim")(facts, 240)
    shipped = MAN.need("paged_decode")(facts, 240)
    kv = 2.0 * 6 * 1_000_000 * 4 * 128 * 2
    qo = 2.0 * 64 * 6 * 40 * 20 * 128 * 2
    assert own == {"flops": 4.0 * 6 * 1_000_000 * 20 * 128, "bytes": kv + qo}
    assert shipped["bytes"] == 2 * own["bytes"]
    # and a model that states no head size reads as the shipped need does
    xl = MAN.config("gpt2-xl")
    tr = MAN.traffic("decode_backlog")
    f = dict(facts, config=xl, traffic=tr)
    assert MAN.need("paged_decode_head_dim")(f, 1) == \
        MAN.need("paged_decode")(f, 1)


# ------------------------------------------------------------ the rehearsal
def test_rehearsal_goes_through_the_shipped_runner(capsys):
    rc = prun.main(["--workload", CELL, "--seed", str(2**31 + 44),
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
    """The reference put in the program's place, computed in the precision
    below the one stated: over the limit of the mean gap."""
    from perfbench.checks import serve as check

    m = CFG["rehearse"]["model"]
    ref = MAN.reference(CFG)
    params = weights.make_params(MAN.weights(CFG).param_specs(m), 9,
                                 jnp.float32)
    reqs = [traffic_gen.Req(i, [1 + (7 * i + 3 * j) % 500 for j in range(n)],
                            12) for i, n in enumerate((20, 33))]
    # what the float32 reference itself would serve, greedily
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
