"""Host milliseconds per batch in the program's `bls_marshal` span
(backends/jax_tpu.py `_marshal_batch`), on the wall clock the harness
injects into the process tracer."""


def read(ctx):
    spans = [s.end - s.start for s in ctx.spans if s.name == "bls_marshal"]
    return 1e3 * sum(spans) / len(spans) if spans else None
