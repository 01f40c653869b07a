"""The training step's flash-attention forward calls at the model's own
head size — ``head_dim`` where the model states it, ``d_model // n_heads``
where it does not — from ``flash_attention.py``'s count of one call."""

from perfbench.flops import flash_attention
from perfbench.flops.transformer import head_shape


def at_model_shapes(one_call, facts, calls):
    m, train = facts["config"]["model"], facts["config"]["train"]
    H, KH, Dh = head_shape(m)
    one = one_call(train["rows_per_chip"], H, KH, train["seq_len"],
                   m.get("head_dim") or Dh)
    return {k: v * calls for k, v in one.items()}


def need(facts, calls):
    return at_model_shapes(flash_attention.forward, facts, calls)
