"""The program's own timing of a plan rebuild (``RoundRecord.plan_build_ms``,
a host clock around the plan service's build), mean over the window's rounds."""


def read(ctx):
    values = [r.plan_build_ms for r in ctx.records if r.plan_build_ms >= 0]
    return sum(values) / len(values) if values else None
