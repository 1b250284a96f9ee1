"""The one traffic generator: a signed pool from a configuration, a
traffic mix and a seed.

A traffic mix (`traffic/<name>.json`) describes a batch as groups of
signature sets. Each group gives its number of sets, the table keys per
set and the distinct messages per slot, each either a number or the
name of a configuration key, and how a set picks its message: `cycle`
(set i takes message i mod M) or `uniform` (drawn from the seed). The
pool holds `pool_batches` such batches, spread over `slots` slots of
messages; the window cycles through them.

Every batch also has two forged twins. Each differs from its batch in
one set, whose signature is a valid G2 point over another message: twin
0 forges a set in the first half of the batch, twin 1 one in the second
half, so a verifier that skips either half is caught. Submit k is forged
when k is a multiple of `forged_every`, and then takes twin
(k // forged_every) mod 2.

Keys: the table has `validator_rows` rows over `distinct_keys` distinct
keys, row r holding key r mod distinct_keys. The distinct secret keys
are an arithmetic progression sk_j = a + j*b (mod r) from the seed, so a
set's signature is |S|*(a H(m)) + (sum of j)*(b H(m)): two full
multiplications per message and two short ones per set.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

import numpy as np

from . import bls_ref as B


@dataclass(frozen=True)
class PoolSet:
    rows: tuple  # table rows (validator indices) of the set's keys
    message: bytes
    sig: tuple  # affine G2 point ((x0, x1), (y0, y1))


@dataclass(frozen=True)
class PoolBatch:
    sets: tuple
    twins: tuple  # ((set index, forged affine signature), ...) per half


@dataclass(frozen=True)
class Pool:
    secret_keys: tuple  # distinct secret keys; row r holds key r % len
    pubkeys: tuple  # their compressed encodings, same order
    rows: int
    batches: tuple


def _value(v, config: dict) -> int:
    return int(config[v]) if isinstance(v, str) else int(v)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), *stream])
    )


def rlc_seed(seed: int, k: int, stream: int = 2) -> int:
    """The random-linear-combination seed of submit k: fresh per batch."""
    lo, hi = rng_for(seed, stream, k).integers(0, 1 << 32, size=2)
    return int(lo) | int(hi) << 32


def _scalar(rng: np.random.Generator) -> int:
    return int.from_bytes(rng.bytes(32), "big") % (B.R - 1) + 1


class _Signer:
    """Signs sets under the progression keys, caching a*H(m), b*H(m)."""

    def __init__(self, a: int, b: int, d: int):
        self.a, self.b, self.d = a, b, d
        self._per_msg: dict = {}

    def sign(self, rows, message: bytes):
        hit = self._per_msg.get(message)
        if hit is None:
            h = B.hash_to_g2(message)
            hit = self._per_msg[message] = (
                B.mul(B.FP2, h, self.a), B.mul(B.FP2, h, self.b)
            )
        am, bm = hit
        count = len(rows)
        j_sum = sum(int(r) % self.d for r in rows)
        point = B.add(B.FP2, B.mul(B.FP2, am, count), B.mul(B.FP2, bm, j_sum))
        return B.affine(B.FP2, point)


def messages_of(seed: int, slot: int, group: str, count: int) -> list:
    return [
        hashlib.sha256(f"bench {seed} slot {slot} {group} {i}".encode()).digest()
        for i in range(count)
    ]


def forged_message(message: bytes) -> bytes:
    return hashlib.sha256(b"forged " + message).digest()


def build_pool(config: dict, traffic: dict, seed: int) -> Pool:
    rows = int(config["validator_rows"])
    d = int(config["distinct_keys"])
    rng = rng_for(seed, 0)
    a, b = _scalar(rng), _scalar(rng)
    secret_keys = tuple((a + j * b) % B.R for j in range(d))
    if 0 in secret_keys:
        raise ValueError("a secret key of the progression is zero")
    step = B.mul(B.FP, B.G1_GEN, b)
    point = B.mul(B.FP, B.G1_GEN, a)
    pubkeys = []
    for _ in range(d):
        pubkeys.append(B.g1_compress(point))
        point = B.add(B.FP, point, step)

    signer = _Signer(a, b, d)
    slots = int(traffic["slots"])
    groups = traffic["groups"]
    msgs = {
        (s, g["name"]): messages_of(seed, s, g["name"], _value(g["messages"], config))
        for s in range(slots)
        for g in groups
    }
    batches = []
    for i in range(int(traffic["pool_batches"])):
        slot = i % slots
        brng = rng_for(seed, 1, i)
        sets = []
        for g in groups:
            pool_msgs = msgs[slot, g["name"]]
            keys = _value(g["keys"], config)
            for j in range(_value(g["sets"], config)):
                if g["draw"] == "cycle":
                    m = pool_msgs[j % len(pool_msgs)]
                elif g["draw"] == "uniform":
                    m = pool_msgs[int(brng.integers(len(pool_msgs)))]
                else:
                    raise ValueError(f"unknown message draw {g['draw']!r}")
                r = tuple(int(x) for x in brng.choice(rows, size=keys, replace=False))
                sets.append(PoolSet(r, m, signer.sign(r, m)))
        n = len(sets)
        twins = []
        for lo, hi in ((0, n // 2), (n // 2, n)):
            idx = int(brng.integers(lo, hi))
            s = sets[idx]
            twins.append((idx, signer.sign(s.rows, forged_message(s.message))))
        batches.append(PoolBatch(tuple(sets), tuple(twins)))
    return Pool(secret_keys, tuple(pubkeys), rows, tuple(batches))


def submission(traffic: dict, pool: Pool, k: int):
    """(pool batch index, twin index or None) of submit k."""
    every = int(traffic["forged_every"])
    twin = (k // every) % 2 if k % every == 0 else None
    return k % len(pool.batches), twin


def batch_sets(pool: Pool, batch: int, twin):
    """The pool sets of one submission as (rows, message, signature)."""
    b = pool.batches[batch]
    out = [(s.rows, s.message, s.sig) for s in b.sets]
    if twin is not None:
        idx, sig = b.twins[twin]
        rows, message, _ = out[idx]
        out[idx] = (rows, message, sig)
    return out
