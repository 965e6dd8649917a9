"""Admission lookups of the adapter memory that found the adapter's page
resident, over all its lookups in the window, in percent."""


def read(ctx):
    hits = ctx.mem1["hits"] - ctx.mem0["hits"]
    lookups = hits + ctx.mem1["misses"] - ctx.mem0["misses"]
    return 100.0 * hits / lookups if lookups else None
