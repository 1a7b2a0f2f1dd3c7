"""Peak device memory in GiB: ``peak_bytes_in_use`` of the fullest chip,
read after the window and before the reference runs."""


def read(ctx):
    peak = ctx.get("peak_bytes")
    return None if peak is None else peak / 2**30
