"""The training step's flash-attention forward calls at the shapes of a
configuration whose model states its head size itself (``head_dim``, not
``d_model // n_heads``), from ``flash_attention.py``'s count of one call."""

from perfbench.flops import flash_attention


def at_model_shapes(one_call, facts, calls):
    m, train = facts["config"]["model"], facts["config"]["train"]
    one = one_call(train["rows_per_chip"], m["n_heads"], m["n_kv_heads"],
                   train["seq_len"], m["head_dim"])
    return {k: v * calls for k, v in one.items()}


def need(facts, calls):
    return at_model_shapes(flash_attention.forward, facts, calls)
