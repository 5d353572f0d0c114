"""Model operations of a GPT-2 step, for the whole step's share of the
chip's peak (`step_mfu.*`).  The PaLM-appendix count that `bench.py` has
used: a trained token costs 6 N for the weight products of the forward
and backward passes (N every parameter, the tied head once) plus causal
attention 6 L h S (12 L h S halved by the mask).  A served token costs a
third of that: 2 N plus 2 x 2 L h ctx for the ctx tokens it attends to.
Recomputed operations are not counted."""
from __future__ import annotations


def train_flops_per_token(n_params: int, n_layer: int, n_embd: int,
                          seq: int) -> float:
    return 6.0 * n_params + 6.0 * n_layer * n_embd * seq


def serve_flops_per_token(n_params: int, n_layer: int, n_embd: int,
                          ctx: float) -> float:
    """``ctx``: how many tokens this one attends to (itself included)."""
    return 2.0 * n_params + 4.0 * n_layer * n_embd * ctx
