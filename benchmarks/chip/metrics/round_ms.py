"""round_ms: the window's wall time over the rounds it completed (host
clock around one ``run(n)``, ended by ``block_until_ready``)."""


def read(ctx):
    return ctx.window_s / ctx.window_rounds * 1e3
