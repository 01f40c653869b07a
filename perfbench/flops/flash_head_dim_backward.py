"""The training step's flash-attention backward calls, the head size read
from the model's ``head_dim``.  A call is found by its ONE ``flash_bwd_dq``
event: the dK/dV kernel is launched once for each resident chunk of the keys
(one at T = 4096, two at T = 8192, ``ops/flash_attention.py``), so its events
are counted for their time and for no need of their own
(``flash_head_dim_time_only.py``)."""

from perfbench.flops import flash_attention
from perfbench.flops.flash_head_dim_forward import at_model_shapes


def need(facts, calls):
    return at_model_shapes(flash_attention.backward, facts, calls)
