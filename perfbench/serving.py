"""What the two serving runners share: the engine as a deployment builds
it, a recorder the scheduler reports its lifecycle events to (stamped on the
benchmark's clock), the tick loop's bookkeeping and the served-token check."""

from __future__ import annotations

from typing import Any, Dict, List, Sequence

from perfbench import traffic_gen
from perfbench.spans import Clock


class Recorder:
    """The scheduler's ``timeline=``: it calls ``record`` at every request
    lifecycle event.  Only the *kind* of event and its counts are taken
    from the program; every time is read here, from the benchmark's clock,
    when the event is reported (after the device readback that produced the
    token)."""

    dropped = 0

    def __init__(self, clock: Clock, prompt_len: Dict[int, int]):
        self.clock = clock
        self.prompt_len = prompt_len
        self.admit: Dict[int, float] = {}
        self.token_times: Dict[int, List[float]] = {}
        self.retired: Dict[int, float] = {}
        self.n_tokens = 0
        self.prefill_calls = 0
        self.prefill_tokens = 0
        self.prefills_done = 0
        self.decode_steps = 0
        self.live_per_step: List[int] = []
        #: kv positions the decode step had to read, per step
        self.context_per_step: List[int] = []

    def events(self):
        return []

    def _token(self, req: int, now: float) -> None:
        self.token_times.setdefault(req, []).append(now)
        self.n_tokens += 1

    def record(self, kind, t=0.0, req=None, slot=None, dur_ms=0.0, info=None):
        now = self.clock.now()
        if kind == "admit":
            self.admit.setdefault(req, now)
        elif kind == "prefill":
            self.prefill_calls += 1
            self.prefill_tokens += info["end"] - info["p0"]
            if info["final"]:
                self.prefills_done += 1
                self._token(req, now)
        elif kind == "decode":
            ids = [rid for _, rid in info["reqs"]]
            self.decode_steps += 1
            self.live_per_step.append(len(ids))
            self.context_per_step.append(sum(
                self.prompt_len[r] + len(self.token_times.get(r, ()))
                for r in ids))
            for r in ids:
                self._token(r, now)
        elif kind == "retire":
            self.retired[req] = now


def build_model(man, cfg: Dict[str, Any], rehearse: bool):
    """The configuration's program (its ``program`` key) at the served
    dtypes, and the spec tree its weights are drawn from."""
    import jax.numpy as jnp

    m = dict(cfg["rehearse"]["model"] if rehearse else cfg["model"])
    dt = jnp.float32 if rehearse else getattr(jnp, cfg["dtype"]["compute"])
    pdt = jnp.float32 if rehearse else getattr(jnp, cfg["dtype"]["params"])
    model = man.program(cfg)(dtype=dt, param_dtype=pdt,
                             decode_attention="fused", **m)
    return model, m, pdt, man.weights(cfg).param_specs(m)


def build_engine(cfg: Dict[str, Any], model, params, rehearse: bool):
    from chainermn_tpu.serving import DecodeEngine

    sv = cfg["rehearse"]["serve"] if rehearse else cfg["serve"]
    max_blocks = sv["max_ctx"] // sv["block_len"]
    eng = DecodeEngine(
        model, params, capacity=sv["capacity"], num_blocks=sv["num_blocks"],
        block_len=sv["block_len"], max_blocks_per_slot=max_blocks,
        prefill_chunk=sv["prefill_chunk"],
        prefix_cache=sv.get("prefix_cache", True),
    )
    return eng, sv


def new_scheduler(eng, clock: Clock, reqs: Sequence[traffic_gen.Req]):
    from chainermn_tpu.serving import Scheduler

    rec = Recorder(clock, {r.id: len(r.prompt) for r in reqs})
    return Scheduler(eng, clock=clock, timeline=rec), rec


def submit(sched, r: traffic_gen.Req, arrival: float) -> None:
    from chainermn_tpu.serving import Request

    sched.submit(Request(id=r.id, prompt=r.prompt, max_new_tokens=r.max_new,
                         arrival=arrival))


def warm_programs(eng, clock: Clock, vocab: int, chunk: int) -> None:
    """Compile (or load) the decode step and every prefill ladder size the
    traffic can reach, by serving throw-away requests whose prompt tails
    land on each size — and, with a prefix cache, the copy-on-write program:
    one more request that shares the first of them's leading tokens up to
    the middle of a block, which a prefix hit between two random prompts
    does inside the window at no fixed seed."""
    def serve(reqs):
        sched, _ = new_scheduler(eng, clock, reqs)
        for r in reqs:
            submit(sched, r, 0.0)
        while sched.pending:
            if not sched.tick():
                raise RuntimeError("warm-up made no progress")

    reqs = [traffic_gen.Req(-1 - i, [1 + (7 * i + j) % (vocab - 1)
                                     for j in range(chunk + size)], 2)
            for i, size in enumerate(eng.prefill_ladder)]
    serve(reqs)
    if eng.prefix is not None:
        shared = max(1, eng.block_len // 2)
        tail = [1 + (3 + 5 * j) % (vocab - 1) for j in range(chunk)]
        serve([traffic_gen.Req(-1 - len(reqs),
                               reqs[0].prompt[:shared] + tail, 2)])
    eng.drop_prefix_cache()


def free_engine(eng) -> None:
    """Release the KV pools so the reference has the memory."""
    import jax

    for leaf in jax.tree_util.tree_leaves((eng.pools, eng.draft_pools)):
        try:
            leaf.delete()
        except Exception:
            pass
    eng.pools = None


def served(sched) -> Dict[int, List[int]]:
    """Every token the window's requests were served: finished requests
    from their completions, the others from the scheduler's harvest."""
    out = {c.id: list(c.tokens) for c in sched.completions
           if c.status == "ok"}
    for e in sched.harvest_entries():
        if e.carried:
            out[e.req.id] = list(e.carried)
    return out


def free_params(eng, params) -> None:
    """Release the weights once nothing reads them any more (the compile
    watcher's registry keeps a finished engine, and so its weights, alive;
    ``perfbench.control`` builds one engine per seed in one process)."""
    import jax

    eng.params = None
    for leaf in jax.tree_util.tree_leaves(params):
        try:
            leaf.delete()
        except Exception:
            pass
