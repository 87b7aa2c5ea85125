"""Mean host time per round of the round engine's local work and aggregation,
with its blocking loss fetch (``FederatedServer._phase_local_work``): the
``local_work`` span."""


def read(ctx):
    return ctx.span_ms_per_round("local_work")
