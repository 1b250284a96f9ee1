"""Batch shapes that missed the compile caches inside the window: the
change of the program's `tpu_compile_cache_misses_total`."""


def read(ctx):
    return ctx.counters.get("tpu_compile_cache_misses_total")
