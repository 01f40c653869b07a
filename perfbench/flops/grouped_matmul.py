"""What one launch of the expert layer's grouped matmul needs
(``ops/grouped_matmul.py``: ``%grouped_matmul`` — ``x . W`` or ``dy . W^T`` —
and ``%grouped_matmul_dw`` — ``x^T . dy``): the held experts' rows times one
``d_model x d_expert`` matrix each, all three kinds of launch alike.

The rows are the *expected* ones, ``tokens x experts_per_tok x held / all``
(6,144 in the shipped cell): a step's true count rides its ``metrics``
(``moe_pairs_held``), which the ``train_steps`` runner does not hand to a
reducer, and swings a few per cent around this by layer and seed.  The
kernel also multiplies the zeros that fill each expert's last tile; those
are no part of what is needed.  Bytes: the rows in and out once and every
held expert's matrix once, in the compute dtype.
"""

import numpy as np


def need(facts, calls):
    cfg = facts["config"]
    m, tr = cfg["model"], cfg["train"]
    rows = (tr["rows_per_chip"] * tr["seq_len"] * m["experts_per_tok"]
            / m["ep_of"])
    D, F, E = m["d_model"], m["d_expert"], m["experts_held"]
    itemsize = np.dtype("float32" if cfg["dtype"]["compute"] == "float32"
                        else "float16").itemsize
    return {"flops": calls * 2.0 * rows * D * F,
            "bytes": calls * itemsize * (rows * (D + F) + E * D * F)}
