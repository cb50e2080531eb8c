"""Query rows per device pass of the serve engine over the window."""


def read(ctx):
    before, after = ctx.record.get("serve_before"), ctx.record.get("serve_after")
    if not before or not after:
        return None
    passes = after["n_batches"] - before["n_batches"]
    if passes <= 0:
        return None
    return (after["n_queries"] - before["n_queries"]) / passes
