"""What one call of decode attention over a paged K/V cache needs, from
its shapes, whatever implements it (today
`paddle_tpu/ops/pallas/paged_attention.py`).

One query row a sequence and head attends to ``ctx`` cached tokens:
Q K^T and P V are 4 h ctx d operations a sequence.  The bytes are the
need of the algorithm, not of the paging: the ``ctx`` keys and values of
every head read once, q in and o out.  Reading whole pages past a
sequence's end, or a pool-sized copy, is the implementation's cost and
is not counted.  With one row against a long cache the call is bound by
bandwidth (4 operations a K/V element pair against 2 x itemsize bytes).
"""
from __future__ import annotations

import re

PALLAS = 'custom_call_target="tpu_custom_call"'


def classify(op_text: str, h: int, num_pages: int, d: int) -> bool:
    """Whether a device operation is the paged-attention kernel, from its
    text in the trace (the HLO instruction): a Pallas call one of whose
    operands is a layer's page pool, [heads, pages, page, d]."""
    return PALLAS in op_text and re.search(
        rf"\[{h},{num_pages},\d+,{d}\]", op_text) is not None


def call(ctx_lens, h, d, q_rows=1, kv_itemsize=4, q_itemsize=4) -> dict:
    """``ctx_lens``: cached tokens of each sequence in the call;
    ``q_rows`` query rows a sequence (1 for decode)."""
    total_ctx = float(sum(ctx_lens))
    n = len(ctx_lens)
    return {"flops": 4.0 * h * d * q_rows * total_ctx,
            "bytes": 2.0 * h * d * kv_itemsize * total_ctx
            + 2.0 * n * q_rows * h * d * q_itemsize}
