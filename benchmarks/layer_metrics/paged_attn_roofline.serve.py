"""Kernel: the paged-attention kernel's share of its roofline in the
traced window.  The need (`kernels/paged_attention.py`) is worked out from
what the clients saw: every output token that arrived in the traced window
was one decode row over the tokens before it, in every layer.  Prefill
chunks pass through the same kernel and their need is not counted, so the
share reads low, never high.  Bandwidth bounds it."""
from benchmarks import harness


def read(ctx):
    trace, peaks = ctx.get("trace"), ctx.get("peaks")
    if not trace or not peaks:
        return None
    k = harness.load_module("kernels", "paged_attention")
    cell = ctx["cell"]
    cfg = cell.config
    heads, d = cfg["n_head"], cfg["n_embd"] // cfg["n_head"]
    seconds = sum(s for name, s in trace["op_seconds"].items()
                  if k.classify(trace["op_text"][name], heads,
                                cell.spec["engine"]["num_pages"], d))
    if seconds <= 0:
        return None
    ctx_lens = [len(req["prompt"]) + i
                for req, rec in zip(ctx["requests"], ctx["records"])
                for i, t in enumerate(rec["token_s"])
                if i > 0 and t <= trace["window_s"]]
    if not ctx_lens:
        return None
    need = k.call(ctx_lens, heads, d)
    least = cfg["n_layer"] * max(need["flops"] / peaks["flops_bf16"],
                                 need["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
