"""What the program writes for the profiler, at the rehearsal size on the
CPU: every scope of the path taken is in the lowered text and none of
another path; a profiled decode run yields the ``cmn_serve_*`` /
``cmn_engine_*`` spans, nested, with counts that agree with what the
benchmark's own ``Recorder`` counted."""

import jax
import jax.numpy as jnp
import pytest

from perfbench import program_trace as pt
from perfbench import serving, traffic_gen, weights
from perfbench.manifest import Manifest
from perfbench.spans import Clock

pytestmark = pytest.mark.tier1


def _lowered(fn, *args, **kwargs) -> str:
    return fn.lower(*args, **kwargs).as_text(debug_info=True)


@pytest.fixture(scope="module")
def served():
    """A rehearsal-size engine that has served a few requests under a
    profiler session: ``(engine, program trace, recorder, block_len)``."""
    import tempfile

    man = Manifest()
    cfg = man.config("gpt2-xl")
    model, m, pdt, specs = serving.build_model(man, cfg, rehearse=True)
    params = weights.make_params(specs, 7, pdt)
    eng, sv = serving.build_engine(cfg, model, params, rehearse=True)
    clock = Clock()
    serving.warm_programs(eng, clock, m["vocab"], sv["prefill_chunk"])
    reqs = [traffic_gen.Req(i, [1 + (5 * i + j) % 500 for j in range(n)], k)
            for i, (n, k) in enumerate([(9, 6), (20, 5), (33, 7), (12, 6),
                                        (17, 4)])]
    sched, rec = serving.new_scheduler(eng, clock, reqs)
    for r in reqs:
        serving.submit(sched, r, 0.0)
    trace_dir = tempfile.mkdtemp(prefix="cmn_prog_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("pb:window"):
            while sched.pending:
                with jax.profiler.TraceAnnotation("pb:tick"):
                    assert sched.tick()
    finally:
        jax.profiler.stop_trace()
    import glob
    import os

    [path] = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                    "*.xplane.pb"))
    t = pt.load(path)
    assert t is not None, "no xplane_pb2 to read the trace with"
    return eng, t, rec, sv["block_len"]


def test_engine_programs_carry_the_scopes_of_their_own_path(served):
    eng = served[0]
    a, kw = eng._step._abstract
    step = _lowered(eng._step, *a, **kw)
    a, kw = eng._prefill._abstract
    prefill = _lowered(eng._prefill, *a, **kw)
    for scope in ("embed", "attn_qkv", "kv_write", "attn.paged", "attn_out",
                  "ffn", "head", "sample"):
        assert scope in step, scope
    for other in ("attn.gathered", "attn.fused", "attn.kv_major_einsum",
                  "attn.einsum", "attn.flash", "attn.xla"):
        assert other not in step, other
    for scope in ("embed", "attn_qkv", "kv_write", "attn.gathered",
                  "attn_out", "ffn", "head", "sample"):
        assert scope in prefill, scope
    for other in ("attn.paged", "attn.fused", "attn.einsum", "attn.flash"):
        assert other not in prefill, other
    # the jitted functions keep the names the benchmark finds them by
    assert "jit_step_impl" in step or "step_impl" in step
    assert "prefill_impl" in prefill


def _train_step_text(attention):
    import chainermn_tpu as cmn
    from perfbench.optim import adafactor

    man = Manifest()
    full = man.config("starcoder2-3b")
    cfg = full["rehearse"]
    m = dict(cfg["model"], attention=attention)
    T = cfg["train"]["seq_len"]
    comm = cmn.create_communicator("xla", devices=jax.devices()[:1])
    model = man.program(full)(dtype=jnp.float32, param_dtype=jnp.float32,
                              **m)
    opt = cmn.create_multi_node_optimizer(
        adafactor.make(cfg["train"]["learning_rate"]), comm)
    state = opt.init(weights.make_params(
        man.weights(full).param_specs(m), 3, jnp.float32))
    step = opt.make_train_step(
        man.loss(full)(model, chunk_size=cfg["train"]["ce_chunk"]),
        has_aux=True)
    batch = (jnp.zeros((1, T), jnp.int32), jnp.zeros((1, T), jnp.int32))
    return _lowered(step, state, batch)


@pytest.mark.parametrize("attention,taken", [
    ("xla", "attn.xla"), ("flash", "attn.flash")])
def test_train_step_carries_the_scopes_of_its_own_path(attention, taken):
    """(The kernels' own names — ``flash_fwd`` ... — exist only where Mosaic
    compiles them: ``tests/ops_tests/test_tpu_compile.py`` pins those.)"""
    text = _train_step_text(attention)
    for scope in ("loss_and_grad", "cmn_allreduce_grads", "optimizer_update",
                  "apply_updates", "embed", "attn_qkv", taken, "attn_out",
                  "ffn", "ce", "rematted_computation", "transpose(jvp("):
        assert scope in text, scope
    other = {"attn.xla", "attn.flash"} - {taken}
    for scope in sorted(other) + ["attn.paged", "attn.gathered", "kv_write",
                                  "attn.einsum", "sample"]:
        assert scope not in text, scope


def test_profiled_ticks_yield_the_serving_spans_correctly_nested(served):
    _, t, rec, _ = served
    names = {s.name for s in t.spans}
    for name in ("cmn_serve_tick", "cmn_serve_deadlines", "cmn_serve_admit",
                 "cmn_serve_prefill_round", "cmn_serve_prefill",
                 "cmn_serve_decode", "cmn_serve_build", "cmn_serve_emit",
                 "cmn_serve_publish", "cmn_engine_upload",
                 "cmn_engine_dispatch", "cmn_engine_readback",
                 "cmn_dispatch"):
        assert name in names, name

    def parent(s):
        return t.spans[s.parent].name if s.parent >= 0 else None

    want = {"cmn_serve_tick": {"pb:tick"},
            "cmn_serve_deadlines": {"cmn_serve_tick"},
            "cmn_serve_admit": {"cmn_serve_tick"},
            "cmn_serve_prefill_round": {"cmn_serve_tick"},
            "cmn_serve_prefill": {"cmn_serve_prefill_round"},
            "cmn_serve_decode": {"cmn_serve_tick"},
            "cmn_serve_build": {"cmn_serve_decode"},
            "cmn_serve_emit": {"cmn_serve_decode"},
            "cmn_serve_publish": {"cmn_serve_decode", "cmn_serve_tick"},
            "cmn_engine_upload": {"cmn_serve_decode", "cmn_serve_prefill"},
            "cmn_engine_dispatch": {"cmn_serve_decode", "cmn_serve_prefill"},
            "cmn_engine_readback": {"cmn_serve_decode", "cmn_serve_prefill"},
            "cmn_dispatch": {"cmn_engine_dispatch"}}
    for s in t.spans:
        if s.name in want:
            assert parent(s) in want[s.name], (s.name, parent(s))
    ticks = t.named("cmn_serve_tick")
    assert len(ticks) == len(t.named("pb:tick"))
    assert [s.stats["program"] for s in t.named("cmn_engine_dispatch")
            if parent(s) == "cmn_serve_decode"] == (
        ["decode_step"] * len(t.named("cmn_serve_decode")))
    # every prefill chunk the recorder saw is a span of one request
    chunks = t.named("cmn_serve_prefill")
    assert len(chunks) == rec.prefill_calls
    assert sum(s.stats["tokens"] for s in chunks) == rec.prefill_tokens
    assert sum(s.stats["final"] for s in chunks) == rec.prefills_done
    assert {s.stats["req"] for s in chunks} == set(rec.admit)
    assert sum(s.stats.get("admitted", 0)
               for s in t.named("cmn_serve_admit")) == len(rec.admit)
    assert sum(s.stats["chunks"] for s in
               t.named("cmn_serve_prefill_round")) == rec.prefill_calls


def test_decode_counts_agree_with_the_benchmarks_recorder(served):
    eng, t, rec, block_len = served
    decodes = t.named("cmn_serve_decode")
    assert len(decodes) == rec.decode_steps
    assert [s.stats["live"] for s in decodes] == rec.live_per_step
    for s, context in zip(decodes, rec.context_per_step):
        st = s.stats
        assert st["table_width"] == eng.max_blocks
        assert st["kv_blocks_grid"] == eng.capacity * eng.max_blocks
        assert 0 < st["kv_blocks_resident"] <= st["kv_blocks_grid"]
        # the blocks that hold a token cover the contexts the step reads,
        # to within one block per live slot
        assert st["kv_blocks_resident"] * block_len >= context
        assert (st["kv_blocks_resident"] - st["live"]) * block_len < context
    emits = t.named("cmn_serve_emit")
    assert sum(s.stats["tokens"] for s in emits) == sum(rec.live_per_step)
    assert sum(s.stats["retired"] for s in emits) <= len(rec.retired)
    man = Manifest()
    facts = {"program_trace": t, "traced_units": len(decodes)}
    got = man.reducer("span_stat_ratio").reduce(
        facts, {"span": "cmn_serve_decode", "num": "kv_blocks_resident",
                "den": "kv_blocks_grid"})
    want = 100.0 * sum(s.stats["kv_blocks_resident"] for s in decodes) / sum(
        s.stats["kv_blocks_grid"] for s in decodes)
    assert got == pytest.approx(want) and 0 < got <= 100
