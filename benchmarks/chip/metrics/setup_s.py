"""setup_s: process start to the first timed round (host clock): imports,
data generation, packing and upload, compilation or the compile cache,
the checked first chunk and the warm chunk that sizes the window."""


def read(ctx):
    return ctx.setup_s
