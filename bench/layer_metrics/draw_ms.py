"""Mean host time per round of the sampler draw
(``FederatedServer._phase_draw``): the ``draw`` span."""


def read(ctx):
    return ctx.span_ms_per_round("draw")
