"""offload_step_ms (ms): the window's time over the optimizer steps
completed in it, in cells whose checkpoints leave the device."""


def read(ctx):
    return 1e3 * ctx.window_s / ctx.steps
