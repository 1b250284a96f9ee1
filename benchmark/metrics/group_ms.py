"""Median device milliseconds of one launch of `_stage_group`, from the trace."""


def read(ctx):
    return ctx.trace.ms_per_launch("_stage_group")
