"""Graceful BLS backend degradation: jax_tpu primary, cpu oracle fallback.

A device fault in the TPU batch verifier (XLA runtime error, a lost
chip, injected FaultPlan error/hang) must never stall signature
verification -- a stalled verifier stalls the whole chain (PAPERS:
committee-based consensus, arXiv:2302.00418). ``FallbackBackend``
implements the same module duck type the other backends expose
(`verify_signature_sets` / `aggregate_verify`) and:

  * routes to the primary while its circuit breaker is closed;
  * on any primary failure, records the failure, surfaces the switch in
    metrics (bls_backend_fallback_total / bls_backend_using_fallback),
    and re-runs the WHOLE batch on the fallback -- batch verification is
    all-or-nothing, so results are identical to an unfaulted fallback
    run;
  * re-probes the primary through the breaker's half-open budget, so a
    recovered device wins the hot path back automatically.

Selected via ``set_backend("fallback")`` (api.py) or embedded directly
with injected backends/breaker for deterministic chaos tests.
"""

from __future__ import annotations

from ....resilience.primitives import CircuitBreaker, EventLog
from ....utils import metrics


class FallbackBackend:
    def __init__(
        self,
        primary=None,
        fallback=None,
        breaker: CircuitBreaker | None = None,
        events: EventLog | None = None,
        primary_name: str = "jax_tpu",
        fallback_name: str = "cpu",
    ):
        self._primary = primary
        self._fallback = fallback
        self.primary_name = primary_name
        self.fallback_name = fallback_name
        self.events = events
        # clock-free breaker: after `denied_budget` degraded batches the
        # primary gets one half-open probe (tests inject a clocked one)
        self.breaker = breaker or CircuitBreaker(
            failure_threshold=1,
            denied_budget=8,
            half_open_probes=1,
            name="bls_primary",
            events=events,
        )

    # backends import lazily: constructing the fallback must not pull in
    # jax when only the cpu path ever runs
    def primary_backend(self):
        if self._primary is None:
            from . import jax_tpu

            self._primary = jax_tpu
        return self._primary

    def fallback_backend(self):
        if self._fallback is None:
            from . import cpu

            self._fallback = cpu
        return self._fallback

    def active_backend_name(self) -> str:
        return (
            self.primary_name
            if self.breaker.state == CircuitBreaker.CLOSED
            else self.fallback_name
        )

    def _run(self, method: str, *args, **kwargs):
        if self.breaker.allow():
            try:
                out = getattr(self.primary_backend(), method)(*args, **kwargs)
            except Exception as e:  # noqa: BLE001 -- ANY primary/device
                # fault degrades to the oracle; the failure is recorded
                # on the breaker and surfaced in metrics, never dropped
                self.breaker.record_failure()
                metrics.BLS_FALLBACK_EVENTS.inc()
                if self.events is not None:
                    self.events.record(
                        "bls_fallback", method=method, error=type(e).__name__
                    )
            else:
                self.breaker.record_success()
                metrics.BLS_USING_FALLBACK.set(0)
                return out
        metrics.BLS_USING_FALLBACK.set(1)
        return getattr(self.fallback_backend(), method)(*args, **kwargs)

    # -- the backend duck type (api.py contract) -----------------------------

    def verify_signature_sets(self, sets, seed=None) -> bool:
        return self._run("verify_signature_sets", sets, seed=seed)

    def aggregate_verify(self, signature, pubkeys, messages) -> bool:
        return self._run("aggregate_verify", signature, pubkeys, messages)


# -- module-level seat for api.set_backend("fallback") ------------------------

_DEFAULT: FallbackBackend | None = None


def get_default() -> FallbackBackend:
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = FallbackBackend()
    return _DEFAULT


def configure(**kwargs) -> FallbackBackend:
    """Replace the module-level instance (tests inject wrapped backends
    and a clocked breaker here, then ``set_backend('fallback')``)."""
    global _DEFAULT
    _DEFAULT = FallbackBackend(**kwargs)
    return _DEFAULT


def verify_signature_sets(sets, seed=None) -> bool:
    return get_default().verify_signature_sets(sets, seed=seed)


def aggregate_verify(signature, pubkeys, messages) -> bool:
    return get_default().aggregate_verify(signature, pubkeys, messages)
