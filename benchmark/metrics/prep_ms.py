"""Median device milliseconds of one launch of `_stage_prep`, from the trace."""


def read(ctx):
    return ctx.trace.ms_per_launch("_stage_prep")
