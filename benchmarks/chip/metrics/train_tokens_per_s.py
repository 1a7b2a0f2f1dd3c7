"""Training tokens per second: the tokens of every gradient the master
applied inside the window, over the window's length (host clock)."""


def read(ctx):
    if not ctx.get("window_s") or ctx.get("tokens") is None:
        return None
    return ctx["tokens"] / ctx["window_s"]
