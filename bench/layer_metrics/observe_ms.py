"""Mean host time per round of the gradient-store scatter and the synchronous
plan rebuild (``StoreBackedSampler.observe_updates``): the ``observe`` span."""


def read(ctx):
    return ctx.span_ms_per_round("observe")
