"""Set-up seconds: from the start of the process to the opening of the
window (weights, rows, the check call, loading and compiling, the window
call's own start-up)."""


def read(ctx):
    return ctx.get("setup_s")
