"""The device's own peak of bytes in use, in GiB."""


def read(ctx):
    peak = ctx["memory"].get("peak_bytes_in_use")
    return peak / 2**30 if peak else None
