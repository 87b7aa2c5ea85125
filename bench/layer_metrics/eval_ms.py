"""Mean host time per round of the test-set evaluation
(``FederatedServer.acc_fn``, its result blocked inside the span); the host-
to-device copy of the test set is outside it, in the ``round`` span's self
time: the ``eval`` span."""


def read(ctx):
    return ctx.span_ms_per_round("eval")
