"""Median device milliseconds of one launch of `_stage_hash`, from the trace."""


def read(ctx):
    return ctx.trace.ms_per_launch("_stage_hash")
