"""Block call: the share of the buckets' rows that are padding, over
every block call of the window (counted in the ``run_batch`` wrap)."""


def read(ctx):
    rows = sum(c["rows"] for c in ctx.calls)
    bucket = sum(c["bucket"] for c in ctx.calls)
    if bucket <= 0:
        return None
    return 100.0 * (bucket - rows) / bucket
