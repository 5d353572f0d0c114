"""Step executables: the model operations of every prompt and output
token the window's requests had processed (`kernels/gpt2_step.py`: 2 N
and the attention over the tokens before it), over the seconds from the
window's start to the last token, and the chip's bf16 peak."""
from benchmarks import harness, weights


def read(ctx):
    peaks, records = ctx.get("peaks"), ctx.get("records")
    if not peaks or not records:
        return None
    cfg = ctx["cell"].config
    count = harness.load_module("kernels", "gpt2_step")
    n = weights.param_count(cfg)
    flops, last = 0.0, 0.0
    for req, rec in zip(ctx["requests"], records):
        if rec["error"] or not rec["token_s"]:
            continue
        tokens = len(req["prompt"]) + len(rec["tokens"]) - 1
        # sum over positions 1..tokens of (2 N + 4 L h position)
        flops += tokens * count.serve_flops_per_token(
            n, cfg["n_layer"], cfg["n_embd"], (tokens + 1) / 2.0)
        last = max(last, rec["token_s"][-1])
    if last <= 0:
        return None
    return 100.0 * flops / last / (peaks["flops_bf16"] * ctx["cell"].chips)
