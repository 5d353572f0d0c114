"""Step executable: model operations a second over the chips' bf16 peak,
at the traced run's own rate."""
from benchmarks import harness, weights


def read(ctx):
    if not ctx.get("tokens_per_s") or not ctx.get("peaks"):
        return None
    cell = ctx["cell"]
    cfg = cell.config
    count = harness.load_module("kernels", "gpt2_step")
    n = weights.param_count(cfg)
    if not cfg.get("tie_word_embeddings", True):
        # an embedding table that is only looked up multiplies nothing
        n -= cfg["padded_vocab_size"] * cfg["n_embd"]
    flops = count.train_flops_per_token(n, cfg["n_layer"], cfg["n_embd"],
                                        cell.traffic["seq"])
    peak = ctx["peaks"]["flops_bf16"] * cell.chips
    return 100.0 * flops * ctx["tokens_per_s"] / peak
