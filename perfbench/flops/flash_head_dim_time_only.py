"""Events that add their time to a roofline and no need: the dK/dV launches
of a flash-attention backward, whose need ``flash_head_dim_backward.py``
counts by the call's one ``flash_bwd_dq`` event."""


def need(facts, calls):
    return {"flops": 0.0, "bytes": 0.0}
