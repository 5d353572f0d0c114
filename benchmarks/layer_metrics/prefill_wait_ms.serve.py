"""Engine: a request's wait from its admission to its first token, which
is its prompt riding the mixed steps chunk by chunk, mean over the
window's first tokens (`decode_stats`: first_token_wait_s / first_tokens)."""


def read(ctx):
    c = ctx.get("counters") or {}
    if not c.get("first_tokens"):
        return None
    return 1e3 * c["first_token_wait_s"] / c["first_tokens"]
