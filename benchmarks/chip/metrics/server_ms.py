"""server_ms: device ms per round under the ``fed.server`` named scope (the
server optimiser's step): the self time of its ops in the traced stretch,
mean over the cell's chips, over the stretch's rounds; 0.0 where no op ran
under it.  Profiler trace (``chipbench.scopes``)."""

SCOPE = "fed.server"


def read(ctx):
    if ctx.layers is None:
        return None
    return ctx.layers.ms_per_round().get(SCOPE, 0.0)
