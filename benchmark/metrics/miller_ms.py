"""Median device milliseconds of one launch of `_stage_miller`, from the trace."""


def read(ctx):
    return ctx.trace.ms_per_launch("_stage_miller")
