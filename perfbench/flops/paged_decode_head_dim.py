"""The decode tick's paged-attention calls at the model's own head size —
``head_dim`` where the model states it (Falcon-H1: 20 / 4 heads of 128 under a
``d_model`` of 5120, where ``d_model // n_heads`` would say 256 and count
twice the bytes), ``d_model // n_heads`` where it does not — from
``paged_decode_attention.py``'s count of one call, as ``paged_decode.py``
counts them: one call per layer per tick, the contexts the benchmark counted
summed over the traced ticks."""

from perfbench.flops import paged_decode_attention


def need(facts, calls):
    m = facts["config"]["model"]
    H = m["n_heads"]
    return paged_decode_attention.call(
        facts["traced_context_tokens"] * m["n_layers"],
        facts["traffic"]["slots"] * m["n_layers"] * facts["traced_units"],
        H, m.get("n_kv_heads") or H, m.get("head_dim") or m["d_model"] // H)
