"""Training steps as ``examples/lm/train_lm.py``'s loop dispatches them,
fed by the prefetch iterators; tokens of whole steps over the time they
took, per chip."""

from __future__ import annotations

import collections
import time
from typing import Any, Dict, List

import numpy as np

from perfbench import device as pdevice
from perfbench import traffic_gen, weights
from perfbench.checks import compare
from perfbench.checks import train as check


def _norm_diff(a, b):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda x, y: jax.tree_util.tree_map(
        lambda p, q: jnp.sqrt(jnp.sum(jnp.square(
            p.astype(jnp.float32) - q.astype(jnp.float32)))), x, y))(a, b)


def reference_steps(m, tcfg, seed, pdt, rows, n_steps, per_step,
                    quant=None) -> Dict[str, Any]:
    """The plain reference follows the first ``n_steps`` steps from the
    same seeded parameters and rows: float32, layer by layer, its own
    Adafactor.  ``quant`` computes it in the control's lower precision."""
    import jax
    import jax.numpy as jnp

    from perfbench.optim.adafactor import leaf_dict
    from perfbench.reference import adafactor as raf
    from perfbench.reference import transformer_lm as ref

    params = dict(weights.make_params(m, seed, pdt))
    state = {k: raf.init(v) for k, v in params.items()}
    use_rope = m.get("pos_enc") == "rope"
    losses, grad_norms = [], {}
    for s in range(n_steps):
        chunk = rows[s * per_step:(s + 1) * per_step]
        toks, tgts = jnp.asarray(chunk[:, :-1]), jnp.asarray(chunk[:, 1:])

        def on_grads(name, g, s=s):
            if s == 0:
                for k, v in leaf_dict(ref._sq(g)).items():
                    grad_norms[f"{name}/{k}" if k else name] = float(np.sqrt(v))
            params[name], state[name] = raf.update(
                params[name], g, state[name], s, tcfg["learning_rate"])

        losses.append(ref.loss_and_grads(
            params, toks, tgts, use_rope=use_rope, quant=quant,
            on_layer_grads=on_grads, offload=per_step > 1))
    p0 = weights.make_params(m, seed, pdt)
    change = leaf_dict(_norm_diff(params, p0))
    return {"losses": losses, "grad_norms": grad_norms,
            "param_change": change}


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import chainermn_tpu as cmn
    from chainermn_tpu.datasets import ArrayDataset
    from chainermn_tpu.iterators import PrefetchIterator
    from chainermn_tpu.models import TransformerLM, lm_loss_chunked

    cfg, tr = ctx.config, ctx.traffic
    if ctx.rehearse:
        tr = dict(tr, **tr.get("rehearse", {}))
        cfg = dict(cfg, **cfg["rehearse"])
    m, tcfg = cfg["model"], cfg["train"]
    dt = jnp.float32 if ctx.rehearse else getattr(jnp, cfg["dtype"]["compute"])
    pdt = jnp.float32 if ctx.rehearse else getattr(jnp, cfg["dtype"]["params"])
    optim = __import__(f"perfbench.optim.{tcfg['optimizer']}",
                       fromlist=["make"])
    T, B = tcfg["seq_len"], tcfg["rows_per_chip"] * ctx.chips

    comm = cmn.create_communicator("xla", devices=jax.devices()[:ctx.chips])
    model = TransformerLM(dtype=dt, param_dtype=pdt, **m)
    everywhere = NamedSharding(comm.mesh, P())
    params = jax.block_until_ready(
        weights.make_params(m, ctx.seed, pdt, sharding=everywhere))
    opt = cmn.create_multi_node_optimizer(
        optim.make(tcfg["learning_rate"]), comm)
    state = opt.init(params)  # eager, as the examples call it
    for leaf in jax.tree_util.tree_leaves(params):
        leaf.delete()  # init copied them
    del params
    jax.block_until_ready(state)
    ctx.mark("init_s")

    rows = traffic_gen.markov_rows(
        tcfg["dataset_rows_per_chip"] * ctx.chips, T, m["vocab"], ctx.seed)
    host_it = PrefetchIterator(
        ArrayDataset(rows[:, :-1], rows[:, 1:]), B, shuffle=False)
    it = cmn.create_device_prefetch_iterator(host_it, comm, depth=2)
    step = opt.make_train_step(
        lm_loss_chunked(model, chunk_size=tcfg["ce_chunk"]), has_aux=True)
    ctx.mark("data_s")

    # The first steps go through the window's own call and feed; the
    # reference follows them afterwards.
    n_follow = cfg["check"]["train"]["steps"]
    prog: Dict[str, Any] = {"losses": []}
    for s in range(n_follow):
        state, metrics = step(state, next(it))
        prog["losses"].append(float(metrics["loss"]))
        if s == 0:
            ctx.mark("compile_or_load_s")
            prog["grad_norms"] = optim.first_grad_norms(
                state.opt_state, state.params)
    p0 = weights.make_params(m, ctx.seed, pdt, sharding=everywhere)
    prog["param_change"] = optim.leaf_dict(_norm_diff(state.params, p0))
    for leaf in jax.tree_util.tree_leaves(p0):
        leaf.delete()
    del p0
    compiles = int(step._cache_size())
    ctx.settle()
    ctx.mark("warm_s")
    setup_s = ctx.setup_seconds()

    # ---------------------------------------------------------- window
    spans, clock = ctx.spans, ctx.clock
    in_flight = tr["in_flight"]
    first, k = tr["trace_from_step"], tr["trace_steps"]
    waiting: collections.deque = collections.deque()
    losses: List[float] = []
    n, traced, window, traced_s = 0, 0, None, 0.0
    t_open = clock.now()
    while clock.now() - t_open < ctx.seconds:
        if ctx.trace and n == first:
            jax.block_until_ready(state)
            ctx.start_trace()
            window = jax.profiler.TraceAnnotation("pb:window")
            window.__enter__()
            t_traced = clock.now()
        with spans("next_batch"):
            batch = next(it)
        with spans("dispatch"):
            state, metrics = step(state, batch)
        waiting.append(metrics["loss"])
        n += 1
        if len(waiting) >= in_flight:
            with spans("wait_step"):
                losses.append(float(waiting.popleft()))
        if window is not None:
            traced += 1
            if traced == k:
                with spans("wait_step"):
                    jax.block_until_ready(state)
                traced_s = clock.now() - t_traced
                window.__exit__(None, None, None)
                ctx.stop_trace()
                window = None
    with spans("wait_step"):
        jax.block_until_ready(state)
    t_close = clock.now()
    gc_seen = ctx.gc_report()
    if window is not None:
        window.__exit__(None, None, None)
        ctx.stop_trace()
    losses.extend(float(x) for x in waiting)
    window_s = t_close - t_open
    recompiled = int(step._cache_size()) - compiles
    peak = pdevice.memory_peak_bytes(ctx.chips)
    loader = "native" if host_it.native else "python"
    it.close()

    # ----------------------------------------------------------- check
    t_check = time.perf_counter()
    for leaf in jax.tree_util.tree_leaves(state):
        leaf.delete()
    del state, batch, metrics
    ref = reference_steps(m, tcfg, ctx.seed, pdt, rows, n_follow, B)
    loose = cfg["check"]["train"].get("loose_leaves", ())
    nums, detail = check.numbers(prog, ref, loose)
    limits = {k: v for k, v in cfg["check"]["train"].items()
              if k.endswith("_gap")}
    ok, compared = compare(nums, limits)
    compared.append({"detail": detail})
    control = {}
    for q in ctx.control:
        low = reference_steps(m, tcfg, ctx.seed, pdt, rows, n_follow, B,
                              quant=q)
        control[q], low_detail = check.numbers(low, ref, loose)
        control[q]["worst_leaves"] = low_detail["worst_leaves"]
    finite = all(np.isfinite(x) for x in losses)
    check_s = time.perf_counter() - t_check

    totals = spans.totals(t_open, t_close)
    values = {
        "train_tokens_per_s": n * B * T / window_s / ctx.chips,
        "setup_s": setup_s,
        "input_wait_ms_step": 1e3 * totals.get("next_batch", 0.0) / max(n, 1),
        "step_ms": 1e3 * window_s / max(n, 1),
    }
    return {
        "correct": ok and finite and recompiled == 0 and compiles == 1,
        "attempted": n, "failed": 0 if finite else n,
        "values": values,
        "facts": {"units": "steps", "traced_units": traced,
                  # a traced window also holds the profiler's start and
                  # stop: its rate is taken over the traced steps alone
                  "traced_rate": (traced * B * T / traced_s / ctx.chips
                                  if traced_s else None),
                  "memory_peak_bytes": peak},
        "compared": compared, "check_s": check_s,
        "sound": nums, "control": control, "gc": gc_seen,
        "memory_peak_bytes": peak,
        "info": {"steps": n, "window_s": window_s, "step_ms": values["step_ms"],
                 "loss_first": losses[0] if losses else None,
                 "loss_last": losses[-1] if losses else None,
                 "step_compiles": compiles, "recompiled_in_window": recompiled,
                 "loader": loader},
        "counts": {"steps": n, "tokens": n * B * T},
    }
