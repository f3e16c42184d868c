"""peak_hbm_mib (MiB): the device's ``peak_bytes_in_use`` read right
after the window, before the reference runs."""


def read(ctx):
    return ctx.peak_bytes / 2 ** 20 if ctx.peak_bytes > 0 else None
