"""Device time per round of the round engine's program (``batched_round_step``),
from the device trace."""


def read(ctx):
    seconds, _ = ctx.device_seconds("batched_round_step", modules=True)
    if seconds is None or not ctx.rounds:
        return None
    return seconds / ctx.rounds * 1e3
