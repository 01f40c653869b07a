"""Perf assertion: PipelineChain (stage-sharded, GPipe microbatching) must
beat the compute-replicated MultiNodeChainList on a stacked-stage model
(VERDICT r1 item 6 — the tier that *should* be faster now has to prove it).

On the shared-core CPU mesh total work is what shows up in wall-clock:
replicated does S full-batch stage computations per device, the pipeline does
(S+M-1) microbatch ones ≈ S/M of the work.  Measured speedup ~1.4× at
S=8, M=4 (see benchmarks/pipeline.py); we assert a conservative margin so the
test stays robust on loaded CI machines.
"""

import sys
import os

# the repo's root, normalised: what is imported through it (``perfbench``
# too, by a later test file) keeps a ``__file__`` that compares equal to paths
# built elsewhere
sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))

from benchmarks.pipeline import measure  # noqa: E402


def test_pipeline_beats_replicated_chain(devices):
    # Best-of-3: wall-clock on the shared-core mesh is noisy when the rest
    # of the suite (or anything else on the box) competes for cores — a
    # single bad sample must not fail the structural claim.
    best = None
    for _ in range(3):
        res = measure(d=256, B=128, M=4, iters=3)
        if best is None or res["speedup"] > best["speedup"]:
            best = res
        if best["speedup"] > 1.1:
            break
    assert best["speedup"] > 1.1, (
        f"PipelineChain ({best['pipeline_s']}s) should beat the replicated "
        f"chain ({best['replicated_s']}s); got speedup {best['speedup']}"
    )
