"""The plain reference: what each verdict of the window should be.

A set {signature, keys, message} verifies when
e(sum of its public keys, H(m)) == e(G1, signature). Every public key
the benchmark imports is sk*G1 for a secret key it knows, so that
equation holds exactly when signature == (sum of the set's secret keys
mod r) * H(m). The reference checks the table's keys against sk*G1 and
each set against that product, computed directly from the secret keys
with no shortcut of the generator's. A batch verifies when every one of
its sets does. It imports nothing of the program.
"""

from __future__ import annotations

from . import bls_ref as B
from .generator import Pool


class ReferenceFault(RuntimeError):
    pass


def check_registry(pool: Pool) -> None:
    """Each distinct key the table imported is sk*G1 of its secret key."""
    for j, (sk, pk) in enumerate(zip(pool.secret_keys, pool.pubkeys)):
        if B.g1_compress(B.mul(B.FP, B.G1_GEN, sk)) != pk:
            raise ReferenceFault(f"table key {j} is not sk*G1")


class Reference:
    def __init__(self, pool: Pool):
        self.pool = pool
        self._h: dict = {}
        self._valid: dict = {}

    def _hash(self, message: bytes):
        h = self._h.get(message)
        if h is None:
            h = self._h[message] = B.hash_to_g2(message)
        return h

    def set_valid(self, rows, message: bytes, sig) -> bool:
        key = (rows, message, sig)
        hit = self._valid.get(key)
        if hit is None:
            keys = self.pool.secret_keys
            sk = sum(keys[r % len(keys)] for r in rows) % B.R
            expected = B.affine(B.FP2, B.mul(B.FP2, self._hash(message), sk))
            hit = self._valid[key] = sig == expected and B.on_curve_g2(sig)
        return hit

    def batch_valid(self, sets) -> bool:
        """`sets` as generator.batch_sets gives them."""
        return all(self.set_valid(*s) for s in sets)
