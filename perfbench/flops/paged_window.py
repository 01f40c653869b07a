"""What a window layer's paged read needs (``ops/decode_attention.py``:
``%paged_decode_window``, one launch a ``W`` layer a decode or mixed step):
the blocks of the slots' rings that hold a visible key, each read once — a
window layer reads ``min(context, window)`` positions a slot and no more, so
this is not ``paged_decode.py``'s count of the contexts.

The blocks are the program's own count: ``cmn_serve_decode.
ring_blocks_resident`` — for the live slots of a tick, the blocks of ONE
window layer's rings from the one with the slot's oldest visible key to the
one it writes — summed over the traced ticks of the unit ledger, times the
model's ``W`` layers.  A block is ``block_len`` rows of every KV head's key
and value.  Operations: two multiply-adds an element read, for each of the
group's query heads.  A program without the count reads nothing."""

from perfbench.flops.grouped_matmul_decode import traced_count


def need(facts, calls):
    cfg = facts["config"]
    m, sv = cfg["model"], cfg["serve"]
    blocks = (traced_count(facts, "cmn_serve_decode.ring_blocks_resident")
              * m["layer_kinds"][:m["n_layers"]].count("W"))
    positions = blocks * sv["block_len"]
    itemsize = 4 if cfg["dtype"]["compute"] == "float32" else 2
    return {"flops": 4.0 * positions * m["n_heads"] * m["head_dim"],
            "bytes": 2.0 * positions * m["n_kv_heads"] * m["head_dim"]
            * itemsize}
