"""The decode step's one-position recurrence (``ops/ssd_scan.py``
``ssd_step``, scope ``ssm.step``) over the traced ticks: the bytes it must
move are the live slots' recurrent state, read once and written once — the
float32 ``(heads, head_dim, state)`` array of every layer; its operands
beside that (one position of x, dt, B, C a slot) are a thousandth of it.
Three multiply-adds an element of state: the decay, the outer product's
addition, the product with C.

The rows are the program's own count: ``cmn_serve_decode.state_rows`` — the
slots whose state a tick's decode program updates — less
``cmn_serve_prefill.rode``, the riding chunks, whose slot goes through the
chunked scan and not through the step; summed over the traced ticks of the
unit ledger.  A slot the step passes over (idle, or prefilling) is rewritten
by a fusion that maps the whole array all the same: that is the
implementation's cost, not the need.  ``calls`` (the matched device events)
is not what is counted.  A program without the count reads nothing."""

from perfbench.reducers import unit_ledger

LEDGER = {"ledger": "serve_tick", "span": "cmn_serve_tick", "ordinal": "tick",
          "from": "trace_from_tick"}


def stepped_rows(facts):
    """Slots stepped, summed over the traced ticks, or ``None``."""
    found = unit_ledger.window(facts, LEDGER)
    if found is None:
        return None
    units, traced = found
    if traced is not None:
        units = [u for u in units if u.ordinal in traced]
    rows = sum(u.counts.get("cmn_serve_decode.state_rows", 0)
               - u.counts.get("cmn_serve_prefill.rode", 0) for u in units)
    return rows if rows > 0 else None


def need(facts, calls):
    m = facts["config"]["model"]
    rows = stepped_rows(facts)
    if rows is None:
        return {"flops": 0.0, "bytes": 0.0}
    elements = (float(rows) * m["n_layers"] * m["ssm_heads"]
                * m["ssm_head_dim"] * m["ssm_state"])
    return {"flops": 6.0 * elements, "bytes": 2.0 * 4.0 * elements}
