"""Kernel: the flash-attention kernels' share of their roofline in the
train step, forward and backward together: the least time the chip could
take for the operations and bytes the calls need (`kernels/
flash_attention.py`) over the kernels' device time in the trace."""
from benchmarks import harness


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not ctx.get("peaks"):
        return None
    k = harness.load_module("kernels", "flash_attention")
    cell = ctx["cell"]
    cfg = cell.config
    heads = cfg["n_head"] // cell.spec.get("head_shards", 1)
    rows = cell.traffic["batch"] // cell.spec.get("batch_shards", 1)
    seq, d = cell.traffic["seq"], cfg["n_embd"] // cfg["n_head"]
    peaks = ctx["peaks"]
    least = seconds = 0.0
    for name, sec in trace["op_seconds"].items():
        which = k.classify(trace["op_text"][name], rows * heads, seq, d)
        if which is None:
            continue
        # the backward need is split over the program's two kernels
        need, share = (k.forward(rows, heads, seq, seq, d), 1.0) \
            if which == "forward" else \
            (k.backward(rows, heads, seq, seq, d), 0.5)
        least += trace["op_counts"][name] * share * max(
            need["flops"] / peaks["flops_bf16"],
            need["bytes"] / peaks["hbm_bytes_per_s"])
        seconds += sec
    if seconds <= 0:
        return None
    return 100.0 * least / seconds
