"""Chip smoke: the BLS batch verifier on a TPU at mainnet slot shape.

One process that imports JAX once and starts no child. It drives the
verification backend through the entry points a beacon node uses:
ValidatorPubkeyCache -> PubkeyTable, api.verify_signature_sets and
api.verify_signature_sets_async -> VerifyPipeline -> backends/jax_tpu,
and BeaconChain.process_block. All on the default backend with default
flags; keys, messages and weights come from --seed.

Phases on one chip (the default):
  table   a 2^20-row validator pubkey table (~1M active validators),
          imported with key_validate on. The one cut: only DISTINCT_KEYS
          distinct keys, repeated to the row count -- host key_validate
          costs ~0.1 s per key, so 2^20 distinct keys would take ~39 h.
  block   128 aggregate-attestation sets of 488 table-gathered pubkeys
          (k_b = 512) over 64 messages: the mega-pairing path. Valid,
          then False with one signature swapped for a valid point over
          the wrong message.
  gossip  64 pre-aggregated single-pubkey sets with distinct messages
          through the async pipeline; a forged batch gives False.
  node    a signed MINIMAL BeaconChainHarness imports blocks through
          BeaconChain.process_block with signatures checked on the chip;
          a block with a wrong-message signature is refused.

`--chips 4` runs the sharded table and the mesh verifier against the
same batch on one device, and nothing else.

Each phase prints one JSON line. The last line is the contract line
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
With no TPU, or when any phase fails, it exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time
from dataclasses import dataclass
from types import SimpleNamespace

import numpy as np

DISTINCT_KEYS = 256


@dataclass(frozen=True)
class Sizes:
    table_rows: int = 1 << 20
    distinct_keys: int = DISTINCT_KEYS
    block_sets: int = 128
    committee: int = 488
    block_messages: int = 64
    gossip_sets: int = 64
    gossip_agg_keys: int = 4
    node_validators: int = 16
    node_blocks: int = 3
    mesh_sets: int = 512


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"kind": devs[0].device_kind, "count": len(devs)}


def device_memory() -> dict:
    """Per device: the allocator's bytes in use (None where the backend
    keeps no statistics) and the bytes of the live jax arrays' buffers
    it holds, each buffer counted once (a shard's view shares its
    array's buffer)."""
    import jax

    devs = jax.devices()
    buffers = {}
    for a in jax.live_arrays():
        for s in a.addressable_shards:
            key = (s.device.id, s.data.unsafe_buffer_pointer())
            buffers[key] = s.data.nbytes
    live = dict.fromkeys((d.id for d in devs), 0)
    for (dev_id, _), nbytes in buffers.items():
        live[dev_id] += nbytes
    return {
        "bytes_in_use": {
            d.id: (d.memory_stats() or {}).get("bytes_in_use") for d in devs
        },
        "live_array_bytes": live,
    }


def check_balanced(memory: dict, slack: int) -> None:
    """No device holds more than `slack` bytes above the lightest one."""
    for name, per_device in memory.items():
        values = list(per_device.values())
        if None in values:
            continue
        check(
            max(values) - min(values) <= slack,
            f"mesh: {name} {per_device} spread more than {slack} B",
        )


def counters() -> dict:
    from lighthouse_tpu.crypto.bls import api
    from lighthouse_tpu.utils import metrics as M

    return {
        "backend": api.get_backend_name(),
        "bls_backend_fallback_total": M.BLS_FALLBACK_EVENTS.value,
        "bls_miller_pairs_last_batch": M.BLS_MILLER_PAIRS_LAST.value,
        "tpu_compile_cache_hits_total": M.TPU_COMPILE_CACHE_HITS.value,
        "tpu_compile_cache_misses_total": M.TPU_COMPILE_CACHE_MISSES.value,
    }


def check_backend(phase: str) -> dict:
    c = counters()
    check(c["backend"] == "jax_tpu", f"{phase}: backend is {c['backend']}")
    check(
        c["bls_backend_fallback_total"] == 0,
        f"{phase}: {c['bls_backend_fallback_total']} fallback batches",
    )
    return c


# --- inputs --------------------------------------------------------------


class Registry:
    """A BeaconState's validator list as ValidatorPubkeyCache reads it
    (`validators[i].pubkey`): `rows` entries cycling over the distinct
    compressed keys."""

    def __init__(self, pubkeys: list, rows: int):
        self._entries = [SimpleNamespace(pubkey=b) for b in pubkeys]
        self._rows = rows

    def __len__(self) -> int:
        return self._rows

    def __getitem__(self, i: int):
        return self._entries[i % len(self._entries)]


def secret_keys(seed: int, n: int) -> list:
    from lighthouse_tpu.crypto.bls import SecretKey
    from lighthouse_tpu.crypto.bls.constants import R

    rng = np.random.default_rng(seed)
    return [
        SecretKey(int.from_bytes(rng.bytes(32), "big") % (R - 1) + 1)
        for _ in range(n)
    ]


def messages(tag: str, seed: int, n: int) -> list:
    return [
        hashlib.sha256(f"chip-smoke {tag} {seed} {j}".encode()).digest()
        for j in range(n)
    ]


def committee_sets(
    cache, sks, seed: int, n_sets: int, k: int, n_msgs: int, copies: int = 1
):
    """`n_sets` aggregate sets of `k` table-tagged pubkeys, each signed
    as (sum of its secret keys) * H(m). Returns (valid sets, the same
    batch with set 0's signature swapped for a valid point over the next
    message).

    With `copies` > 1, n_sets // copies sets are drawn and signed, and
    each is repeated at validator indices shifted by len(cache) // copies:
    rows that hold the same keys, so one G2 multiply signs every copy,
    and on a sharded table each copy is gathered from another shard."""
    from lighthouse_tpu.crypto.bls import Signature, SignatureSet
    from lighthouse_tpu.crypto.bls.constants import R
    from lighthouse_tpu.crypto.bls.hash_to_curve_ref import hash_to_g2

    rng = np.random.default_rng(seed + 1)
    d, rows = len(sks), len(cache)
    base, shift = n_sets // copies, rows // copies
    check(base * copies == n_sets and shift % d == 0, "committee_sets shape")
    msgs = messages("block", seed, n_msgs)
    h = [hash_to_g2(m) for m in msgs]
    drawn = []
    for i in range(base):
        idx = rng.choice(rows, size=k, replace=False).tolist()
        total = sum(sks[j % d].scalar for j in idx) % R
        m = i % n_msgs
        drawn.append((idx, total, m, Signature(h[m].mul(total))))
    sets = [
        SignatureSet.multiple_pubkeys(
            sig, [cache.get((j + c * shift) % rows) for j in idx], msgs[m]
        )
        for c in range(copies)
        for idx, _, m, sig in drawn
    ]
    _, total, m, _ = drawn[0]
    forged = list(sets)
    forged[0] = SignatureSet.multiple_pubkeys(
        Signature(h[(m + 1) % n_msgs].mul(total)),
        sets[0].pubkeys,
        sets[0].message,
    )
    return sets, forged


def gossip_sets(sks, seed: int, n: int, agg: int):
    """`n` sets with one pre-aggregated pubkey each (the sum of `agg`
    validated keys) over distinct messages; plus the batch with set 0
    signed over set 1's message."""
    from lighthouse_tpu.crypto.bls import (
        AggregatePublicKey,
        PublicKey,
        Signature,
        SignatureSet,
    )
    from lighthouse_tpu.crypto.bls.constants import R
    from lighthouse_tpu.crypto.bls.hash_to_curve_ref import hash_to_g2

    d = len(sks)
    msgs = messages("gossip", seed, n)
    sets, totals = [], []
    for i in range(n):
        members = [sks[(agg * i + j) % d] for j in range(agg)]
        point = AggregatePublicKey.aggregate(
            [sk.public_key() for sk in members]
        ).point
        # a sum of validated keys stays in the r-torsion subgroup
        pk = PublicKey(point, subgroup_checked=True)
        total = sum(sk.scalar for sk in members) % R
        sets.append(
            SignatureSet.single_pubkey(
                Signature(hash_to_g2(msgs[i]).mul(total)), pk, msgs[i]
            )
        )
        totals.append(total)
    forged = list(sets)
    forged[0] = SignatureSet.single_pubkey(
        Signature(hash_to_g2(msgs[1 % n]).mul(totals[0])),
        sets[0].pubkeys[0],
        sets[0].message,
    )
    return sets, forged


# --- phases --------------------------------------------------------------


def phase_table(seed: int, sizes: Sizes):
    """The validator pubkey table at mainnet size through the chain's
    ValidatorPubkeyCache, with key_validate on."""
    import jax

    from lighthouse_tpu.chain.pubkey_cache import ValidatorPubkeyCache
    from lighthouse_tpu.crypto.bls import api

    sks = secret_keys(seed, sizes.distinct_keys)
    raw = [sk.public_key().to_bytes() for sk in sks]
    state = SimpleNamespace(validators=Registry(raw, sizes.table_rows))
    memory = {"before_table": device_memory()}
    cache, import_s = timed(lambda: ValidatorPubkeyCache(state))
    dev, upload_s = timed(lambda: jax.block_until_ready(cache.device_table()))
    memory["after_upload"] = device_memory()
    table = cache._table
    rows = sizes.table_rows
    check(len(cache) == rows and len(table) == rows, "table row count")
    check(dev.shape[0] == rows, f"device table has {dev.shape[0]} rows")
    idx = np.random.default_rng(seed).integers(0, rows, size=(16, 64))
    got = jax.block_until_ready(cache.gather(idx))
    memory["after_gather"] = device_memory()
    check(
        np.array_equal(np.asarray(got), table._host[idx]),
        "gathered rows differ from the host table",
    )
    emit(
        "table",
        **device_info(),
        rows=rows,
        distinct_keys=sizes.distinct_keys,
        key_validate=api.key_validate_enabled(),
        import_s=import_s,
        upload_s=upload_s,
        table_bytes=int(dev.nbytes),
        table_devices=len(dev.sharding.device_set),
        sharded=table.sharded,
        gather_devices=len(got.sharding.device_set),
        gather_ok=True,
        device_memory=memory,
    )
    return cache, sks


def phase_block(cache, sks, seed: int, sizes: Sizes) -> None:
    """Block-import batch at committee width: the mega-pairing path over
    rows gathered from the device table."""
    from lighthouse_tpu.crypto.bls import api
    from lighthouse_tpu.crypto.bls.backends.jax_tpu import _bucket
    from lighthouse_tpu.utils import metrics as M

    (sets, forged), build_s = timed(
        lambda: committee_sets(
            cache, sks, seed, sizes.block_sets, sizes.committee,
            sizes.block_messages,
        )
    )
    agg0, gather0 = M.BLS_AGGREGATED_BATCHES.value, M.BLS_GATHER_HITS.value
    ok, first_s = timed(lambda: api.verify_signature_sets(sets, seed=seed))
    pairs = M.BLS_MILLER_PAIRS_LAST.value
    ok2, steady_s = timed(lambda: api.verify_signature_sets(sets, seed=seed + 1))
    bad, forged_s = timed(lambda: api.verify_signature_sets(forged, seed=seed + 2))
    emit(
        "block",
        **device_info(),
        sets=sizes.block_sets,
        pubkeys_per_set=sizes.committee,
        messages=sizes.block_messages,
        build_s=build_s,
        first_call_s=first_s,
        compile_s=first_s - steady_s,
        steady_s=steady_s,
        forged_s=forged_s,
        verdicts={"valid": ok, "valid_reweighted": ok2, "forged": bad},
        **counters(),
    )
    check(ok is True and ok2 is True, f"block: valid batch gave {ok}, {ok2}")
    check(bad is False, f"block: forged batch gave {bad}")
    check(
        M.BLS_AGGREGATED_BATCHES.value - agg0 == 3,
        "block: batches did not take the mega-pairing path",
    )
    check(
        M.BLS_GATHER_HITS.value - gather0 == 3,
        "block: pubkeys were not gathered from the device table",
    )
    check(
        pairs == _bucket(sizes.block_messages) + 1,
        f"block: {pairs} Miller pairs",
    )
    check_backend("block")


def phase_gossip(sks, seed: int, sizes: Sizes) -> None:
    """Gossip batch: distinct messages, host-packed pre-aggregated
    pubkeys, through the async VerifyPipeline."""
    from lighthouse_tpu.crypto.bls import api
    from lighthouse_tpu.crypto.bls.backends.jax_tpu import _bucket
    from lighthouse_tpu.utils import metrics as M

    (sets, forged), build_s = timed(
        lambda: gossip_sets(sks, seed, sizes.gossip_sets, sizes.gossip_agg_keys)
    )
    first, first_s = timed(
        lambda: api.verify_signature_sets_async(sets, seed=seed).result()
    )

    def stream():
        futs = [
            api.verify_signature_sets_async(batch, seed=seed + i)
            for i, batch in enumerate((sets, forged, sets), 1)
        ]
        return [f.result() for f in futs]

    verdicts, steady_s = timed(stream)
    pairs = M.BLS_MILLER_PAIRS_LAST.value
    emit(
        "gossip",
        **device_info(),
        sets=sizes.gossip_sets,
        build_s=build_s,
        first_call_s=first_s,
        compile_s=first_s - steady_s / 3,
        steady_s_per_batch=steady_s / 3,
        verdicts={"valid": first, "stream": verdicts},
        pipeline_occupancy_peak=M.BLS_PIPELINE_OCCUPANCY_PEAK.value,
        **counters(),
    )
    check(first is True, f"gossip: valid batch gave {first}")
    check(verdicts == [True, False, True], f"gossip: verdicts {verdicts}")
    check(pairs == _bucket(sizes.gossip_sets) + 1, f"gossip: {pairs} pairs")
    check_backend("gossip")


def phase_node(seed: int, sizes: Sizes) -> None:
    """The node path: signed blocks imported through process_block with
    bulk signature verification on the chip, and a block whose proposer
    signature is a valid point over the wrong message refused."""
    from lighthouse_tpu.chain.beacon_chain import BlockError
    from lighthouse_tpu.harness.beacon_chain_harness import BeaconChainHarness
    from lighthouse_tpu.types import MINIMAL, ChainSpec, interop_secret_key
    from lighthouse_tpu.utils import metrics as M

    h = BeaconChainHarness(
        sizes.node_validators, MINIMAL, ChainSpec.interop(), sign=True
    )
    pairs0, gather0 = M.BLS_MILLER_PAIRS.value, M.BLS_GATHER_HITS.value
    import_s = []
    for slot in range(1, sizes.node_blocks + 1):
        root, s = timed(lambda: h.add_block_at_slot(slot))
        import_s.append(s)
        check(h.chain.head_root == root, f"node: head did not advance at {slot}")

    slot = sizes.node_blocks + 1
    head = h.chain.head_root
    signed, _ = h.producer.produce_block(
        slot, (), base_state=h.chain._states[head]
    )
    good = signed.signature
    proposer = int(signed.message.proposer_index)
    wrong = seed.to_bytes(8, "little") + bytes(24)
    signed.signature = interop_secret_key(proposer).sign(wrong).to_bytes()
    h.chain.slot_clock.set_slot(slot)
    try:
        h.chain.process_block(signed, strategy=h.strategy)
        refused = None
    except BlockError as e:
        refused = str(e)
    refused_head = h.chain.head_root
    signed.signature = good
    root = h.chain.process_block(signed, strategy=h.strategy)
    emit(
        "node",
        **device_info(),
        preset="minimal",
        validators=sizes.node_validators,
        blocks_imported=sizes.node_blocks + 1,
        import_s=import_s,
        tampered_refused=refused,
        head_slot=int(h.chain._states[h.chain.head_root].slot),
        **counters(),
    )
    check(refused is not None, "node: tampered block was imported")
    check(refused_head == head, "node: head moved on a refused block")
    check(h.chain.head_root == root, "node: untampered block not imported")
    check(M.BLS_MILLER_PAIRS.value > pairs0, "node: no device verification")
    check(M.BLS_GATHER_HITS.value > gather0, "node: no table gather")
    check_backend("node")


def phase_mesh(cache, sks, seed: int, sizes: Sizes) -> None:
    """Four chips: the sharded table and the mesh verifier, against the
    same batch verified on one device in this process."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from lighthouse_tpu.crypto.bls import PublicKey, SignatureSet, api
    from lighthouse_tpu.parallel.verify_sharded import VALIDATOR_AXIS
    from lighthouse_tpu.utils import metrics as M

    devs = jax.devices()
    table = cache._table
    sharding = table._dev.sharding
    check(table.sharded, "mesh: the table is not sharded")
    check(
        isinstance(sharding, NamedSharding)
        and sharding.spec == PartitionSpec(VALIDATOR_AXIS)
        and sharding.mesh.size == len(devs),
        f"mesh: table sharding {sharding}",
    )
    shard_rows = {
        s.device.id: s.data.shape[0] for s in table._dev.addressable_shards
    }
    check(
        sorted(shard_rows) == sorted(d.id for d in devs)
        and set(shard_rows.values()) == {sizes.table_rows // len(devs)},
        f"mesh: table shard rows {shard_rows}",
    )

    (sets, forged), build_s = timed(
        lambda: committee_sets(
            cache, sks, seed, sizes.mesh_sets, sizes.committee,
            sizes.block_messages, copies=len(devs),
        )
    )

    def one_device(batch):
        # the same keys, untagged: host-packed onto the default device
        return [
            SignatureSet.multiple_pubkeys(
                s.signature,
                [PublicKey(k.point, k.to_bytes(), subgroup_checked=True)
                 for k in s.pubkeys],
                s.message,
            )
            for s in batch
        ]

    def on_one_device(batch):
        # the threshold read per call: 0 keeps this batch off the mesh
        key = "LIGHTHOUSE_TPU_SHARD_MIN_SETS"
        saved = os.environ.get(key)
        os.environ[key] = "0"
        try:
            return timed(
                lambda: api.verify_signature_sets(one_device(batch), seed=seed)
            )
        finally:
            if saved is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = saved

    memory = {"before_mesh": device_memory()}
    shrinks0 = M.BLS_MESH_SHRINKS.value
    sharded0 = M.BLS_SHARDED_BATCHES.value
    mesh_ok, mesh_first_s = timed(
        lambda: api.verify_signature_sets(sets, seed=seed)
    )
    mesh_size = M.BLS_SHARD_MESH_SIZE.value
    mesh_bad, mesh_forged_s = timed(
        lambda: api.verify_signature_sets(forged, seed=seed)
    )
    mesh_batches = M.BLS_SHARDED_BATCHES.value - sharded0
    mesh_shrinks = M.BLS_MESH_SHRINKS.value - shrinks0
    # read before the one-device comparison puts anything on device 0
    memory["after_mesh"] = device_memory()
    single_ok, single_first_s = on_one_device(sets)
    single_bad, single_forged_s = on_one_device(forged)
    memory["after_one_device"] = device_memory()
    emit(
        "mesh",
        **device_info(),
        sets=sizes.mesh_sets,
        pubkeys_per_set=sizes.committee,
        messages=sizes.block_messages,
        build_s=build_s,
        bls_shard_mesh_devices=mesh_size,
        mesh_batches=mesh_batches,
        mesh_shrinks=mesh_shrinks,
        mesh_first_call_s=mesh_first_s,
        mesh_forged_s=mesh_forged_s,
        one_device_first_call_s=single_first_s,
        one_device_forged_s=single_forged_s,
        verdicts={
            "mesh": {"valid": mesh_ok, "forged": mesh_bad},
            "one_device": {"valid": single_ok, "forged": single_bad},
        },
        device_memory=memory,
        **counters(),
    )
    check(
        mesh_batches == 2 and mesh_shrinks == 0 and mesh_size == len(devs),
        f"mesh: batches ran on a mesh of {mesh_size}, {mesh_shrinks} shrinks",
    )
    check(
        mesh_ok is True and single_ok is True,
        f"mesh: valid batch gave mesh={mesh_ok} one device={single_ok}",
    )
    check(
        mesh_bad is False and single_bad is False,
        f"mesh: forged batch gave mesh={mesh_bad} one device={single_bad}",
    )
    # nothing of the table or the mesh batches lands on device 0 alone:
    # after the mesh calls no device holds more than half a table slice
    # above the lightest one
    check_balanced(memory["after_mesh"], table._dev.nbytes // len(devs) // 2)
    check_backend("mesh")


def run(seed: int, chips: int, sizes: Sizes = Sizes()) -> None:
    """Every phase of the chosen mode; raises on the first failure."""
    cache, sks = phase_table(seed, sizes)
    if chips == 4:
        phase_mesh(cache, sks, seed, sizes)
        return
    phase_block(cache, sks, seed, sizes)
    phase_gossip(sks, seed, sizes)
    phase_node(seed, sizes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: the sharded table and mesh verifier path only",
    )
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU (JAX platform {devs[0].platform!r})",
            file=sys.stderr,
        )
        return 2
    if len(devs) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
            file=sys.stderr,
        )
        return 2

    from lighthouse_tpu.utils import compile_cache

    emit("start", **device_info(), compile_cache=compile_cache.arm(),
         seed=args.seed)
    run(args.seed, args.chips)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devs[0].platform,
            "kind": devs[0].device_kind,
            "count": len(devs),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
