"""Mean host time per round of the test-set evaluation
(``FederatedServer.acc_fn``, the compiled accuracy call over the test set
that the server staged on the device when it was built, its result blocked
inside the span); no round copies the test set: the ``eval`` span."""


def read(ctx):
    return ctx.span_ms_per_round("eval")
