"""``test_trace_reducers.py`` (PR 23) feeds *every* per-layer metric of the
backlog cell the trace recorded then, which holds nothing the program wrote:
that cut dropped every stat, and the program had no span or scope of its own
yet.  The metrics of PR 24 read the program's side of a trace
(``perfbench.program_trace``), which a run finds as the newest ``.xplane.pb``
on disk; for that one test it is the fixture recorded *with* the program's
spans and scopes (two ticks of the same cell, my chip run, PR 24)."""

import os

import pytest

from perfbench import program_trace
from perfbench.manifest import HERE

PROGRAM_FIXTURE = os.path.join(
    HERE, "fixtures", "serve_backlog_2ticks_program.xplane.pb.gz")


@pytest.fixture(autouse=True)
def _program_side_of_the_recorded_backlog_trace(request, monkeypatch):
    if request.node.name == (
            "test_every_backlog_layer_metric_reads_the_recorded_trace"):
        monkeypatch.setattr(program_trace, "newest_xplane",
                            lambda root=None: PROGRAM_FIXTURE)
