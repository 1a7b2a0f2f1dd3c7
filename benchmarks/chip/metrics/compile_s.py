"""Seconds the process spent tracing, lowering, compiling and loading
programs from the compile cache, up to the end of the window call (the
program's compile counter, ``stats["compile"]["seconds"]``)."""


def read(ctx):
    return ((ctx.get("stats") or {}).get("compile") or {}).get("seconds")
