"""Transport: MB of KV cache (or recurrent state) and logits shipped per
request over the ring, from the set's counters (kv_bytes / kv_pages)."""


def reduce(ctx):
    pages = ctx.counters.get("kv_pages", 0)
    if not pages:
        return None
    return ctx.counters["kv_bytes"] / pages / 1e6
