"""``checks/serve.py served_gaps``: one sampled request a call of the
reference — one ``(1, check_pad)`` shape, one row's logits held — and the
numbers a hand count gives."""

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import traffic_gen
from perfbench.checks import serve as check

pytestmark = pytest.mark.tier1

V, PAD = 23, 16


class Ref:
    """A stand-in reference: after token ``t`` the best next token is
    ``(3 t + 1) % V``, every other ``v`` lying ``(v - best)**2`` below it;
    the lower precision puts the token after the best first."""

    def __init__(self):
        self.shapes = []

    def forward_logits(self, params, toks, model, quant=None):
        self.shapes.append((tuple(toks.shape), quant))
        best = (3 * np.asarray(toks) + 1) % V + (1 if quant else 0)
        return jnp.asarray(-(np.arange(V)[None, None, :]
                             - best[..., None]).astype(np.float32) ** 2)


def _greedy(prompt, n):
    text = list(prompt)
    for _ in range(n):
        text.append((3 * text[-1] + 1) % V)
    return text[len(prompt):]


def test_a_row_a_call_and_the_hand_count():
    reqs = [traffic_gen.Req(0, [5, 6, 7], 4), traffic_gen.Req(1, [2, 9], 6),
            traffic_gen.Req(2, [11], 3)]
    served = {r.id: _greedy(r.prompt, r.max_new) for r in reqs}
    ref = Ref()
    sound = check.served_gaps(ref, {"vocab": V}, None, reqs, served, PAD)
    assert ref.shapes == [((1, PAD), None)] * 3
    assert sound == {"served_gap_widest": 0.0, "served_gap_mean": 0.0,
                     "tokens": 13, "not_best": 0, "requests": 3}
    # one served token two off the reference's best: a gap of 4 there, and
    # the token after it is judged by what was served, so it stays the best
    served[1][2] = (served[1][2] + 2) % V
    served[1][3:] = _greedy([served[1][2]], 3)
    off = check.served_gaps(Ref(), {"vocab": V}, None, reqs, served, PAD)
    assert off["served_gap_widest"] == 4.0 and off["not_best"] == 1
    assert off["served_gap_mean"] == pytest.approx(4.0 / 13)
    ok, rows = check.judge(off, {"served_gap_mean": 0.5,
                                 "served_gap_widest": 3.0})
    assert not ok and [r["ok"] for r in rows[:2]] == [True, False]
    # the control: the lower precision's own first choice at every position,
    # one call of each precision a row
    ref = Ref()
    low = check.served_gaps(ref, {"vocab": V}, None, reqs, served, PAD,
                            quant="fp8")
    assert [q for _, q in ref.shapes] == [None, "fp8"] * 3
    # (one off the best everywhere but where the best is the last token)
    assert low["not_best"] >= 12 and 0.9 < low["served_gap_mean"] <= 1.0
    assert low["served_gap_widest"] == 1.0


def test_the_sample_holds_the_longest_and_is_the_seeds():
    reqs = [traffic_gen.Req(i, [1] * (4 + i % 5), 3) for i in range(20)]
    served = {r.id: [2] * (1 + r.id % 3) for r in reqs if r.id != 7}
    a = check.pick_sample(reqs, served, 4, 2**31 + 5)
    assert len(a) == 4 and a == check.pick_sample(reqs, served, 4, 2**31 + 5)
    longest = max(len(r.prompt) + len(served[r.id]) for r in reqs
                  if r.id in served)
    assert len(a[0].prompt) + len(served[a[0].id]) == longest
    assert 7 not in {r.id for r in a}
    assert {r.id for r in a} != {
        r.id for r in check.pick_sample(reqs, served, 4, 2**31 + 6)}
