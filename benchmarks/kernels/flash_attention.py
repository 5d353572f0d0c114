"""What one call of causal/full softmax attention needs, from its shapes,
whatever implements it (today `paddle_tpu/ops/pallas/flash_attention.py`).

Operations: the forward pass is two matrix products over the score matrix
(Q K^T and P V): 4 b h sq sk d.  The backward pass is five (S again, dP,
dV, dK, dQ — the FlashAttention-2 count for an algorithm that does not keep
P): 10 b h sq sk d.  A causal mask halves what is needed (the lower
triangle, diagonal blocks counted whole would be more; this is the lower
bound of the need: sq (sq + 1) / 2 of sq^2 pairs when sq == sk).
Recomputation beyond that (the program's backward runs two kernels that
each rebuild S and dP) is not needed work and is not counted.

Bytes: every operand read once and every result written once in HBM:
forward q, k, v in and o out (the log-sum-exp, 4 bytes a row, too);
backward q, k, v, o, do and the log-sum-exp in, dq, dk, dv out.
"""
from __future__ import annotations

import re

PALLAS = 'custom_call_target="tpu_custom_call"'


def classify(op_text: str, bh: int, sq: int, d: int):
    """Which attention kernel a device operation is, from its text in the
    trace (the HLO instruction): "forward", "backward" or None.  A Pallas
    call whose operands are [b*h, sq, d] arrays is attention: q, k, v make
    the forward kernel; q, k, v, o, do and the log-sum-exp a backward one
    (the program runs two, one for dk and dv, one for dq).  The operands'
    shapes stand in the operand list or, where that holds names only, in
    the layout constraints."""
    if PALLAS not in op_text:
        return None
    shape = re.compile(rf"\[{bh},{sq},{d}\]")
    operands = op_text.split("custom-call(", 1)[-1].split(
        "custom_call_target", 1)[0]
    shaped = len(shape.findall(operands))
    if not shaped and "operand_layout_constraints=" in op_text:
        shaped = len(shape.findall(op_text.split(
            "operand_layout_constraints=", 1)[1].split("frontend_", 1)[0]))
    if shaped == 3:
        return "forward"
    if shaped >= 5:
        return "backward"
    return None


def _pairs(sq: int, sk: int, causal: bool) -> float:
    """Query-key pairs that are not masked (bottom-right aligned)."""
    if not causal:
        return float(sq * sk)
    full = max(sk - sq, 0)
    tri = min(sq, sk)
    return float(sq * full + tri * (tri + 1) / 2 + max(sq - sk, 0) * 0)


def forward(b, h, sq, sk, d, causal=True, itemsize=2) -> dict:
    pairs = _pairs(sq, sk, causal)
    return {"flops": 4.0 * b * h * pairs * d,
            "bytes": float(b * h * (itemsize * d * (2 * sq + 2 * sk)
                                    + 4 * sq))}


def backward(b, h, sq, sk, d, causal=True, itemsize=2) -> dict:
    pairs = _pairs(sq, sk, causal)
    return {"flops": 10.0 * b * h * pairs * d,
            "bytes": float(b * h * (itemsize * d * (4 * sq + 4 * sk)
                                    + 4 * sq))}
