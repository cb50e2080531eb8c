"""Programs compiled in set-up that JAX's persistent cache did not serve."""


def read(ctx):
    c = ctx.counters.get("setup")
    if not c:
        return None
    return c["cache_requests"] - c["cache_hits"]
