"""Model FLOP/s utilisation: tokens per second per chip times the FLOPs the
forward and backward passes require per token (``perfbench/flops``, no
recomputation), over the chip's bf16 peak.  Not a kernel's roofline."""

from perfbench.flops import transformer


def reduce(facts, args):
    rate = facts.get("traced_rate") or facts["values"].get(args["rate"])
    if rate is None:
        return None
    per_token = transformer.train_flops_per_token(
        facts["config"]["model"], facts["config"]["train"]["seq_len"])
    return 100.0 * rate * per_token / facts["peaks"]["bf16_flops_per_s"]
