"""The device-resident pubkey table wired into the production verify path.

Covers VERDICT r3 item 2: batches whose keys all come from the chain's
ValidatorPubkeyCache are marshaled as validator INDICES (device gather),
with zero per-key host limb packing on the hot path (reference
validator_pubkey_cache.rs:10-23,79,131 -- decompress once, reference by
index thereafter).
"""

from types import SimpleNamespace

import numpy as np
import pytest

from lighthouse_tpu.chain.pubkey_cache import ValidatorPubkeyCache
from lighthouse_tpu.crypto.bls import (
    AggregateSignature,
    SignatureSet,
    set_backend,
)
from lighthouse_tpu.crypto.bls.backends import jax_tpu
from lighthouse_tpu.types.interop import interop_keypair


# device-path tests: the staged verifier compiles in set-up (conftest)
pytestmark = pytest.mark.usefixtures("staged_verifier")


def _registry_state(n):
    return SimpleNamespace(
        validators=[
            SimpleNamespace(pubkey=interop_keypair(i)[1].to_bytes())
            for i in range(n)
        ]
    )


def _tagged_sets(cache, n_sets=4, k=2):
    sets = []
    for i in range(n_sets):
        msg = bytes([i]) * 32
        idxs = [(i * k + j) % len(cache) for j in range(k)]
        sks = [interop_keypair(ix)[0] for ix in idxs]
        agg = AggregateSignature.aggregate([sk.sign(msg) for sk in sks])
        sets.append(
            SignatureSet.multiple_pubkeys(
                agg.to_signature(), [cache.get(ix) for ix in idxs], msg
            )
        )
    return sets


@pytest.fixture(autouse=True)
def _jax_backend():
    set_backend("jax_tpu")
    yield
    set_backend("fake")


def test_indexed_batch_verifies():
    cache = ValidatorPubkeyCache(_registry_state(8))
    sets = _tagged_sets(cache)
    assert jax_tpu._common_table(sets) is cache
    assert jax_tpu.verify_signature_sets(sets, seed=7)


def test_indexed_batch_rejects_bad_signature():
    cache = ValidatorPubkeyCache(_registry_state(8))
    sets = _tagged_sets(cache)
    # swap one set's message: its aggregate no longer matches
    sets[2].message = b"\xff" * 32
    assert not jax_tpu.verify_signature_sets(sets, seed=7)


def test_hot_path_does_no_per_key_limb_packing(monkeypatch):
    cache = ValidatorPubkeyCache(_registry_state(8))
    sets = _tagged_sets(cache)
    cache.device_table()  # upload happens here, once

    def _boom(pk):
        raise AssertionError("hot path packed host limbs for a pubkey")

    monkeypatch.setattr(jax_tpu, "_pk_limbs", _boom)
    assert jax_tpu.verify_signature_sets(sets, seed=7)


def test_mixed_batch_falls_back_to_host_packing():
    cache = ValidatorPubkeyCache(_registry_state(8))
    # three tagged sets and the untagged one fill the 4-set bucket
    sets = _tagged_sets(cache, n_sets=3)
    # one untagged key (e.g. a deposit outside the registry): generic path
    sk, pk = interop_keypair(100)
    msg = b"\x42" * 32
    sets.append(SignatureSet.single_pubkey(sk.sign(msg), pk, msg))
    assert jax_tpu._common_table(sets) is None
    assert jax_tpu.verify_signature_sets(sets, seed=7)


class TestImportSeamKeyValidate:
    """The table import is the key_validate seam (blst runs it at
    decompression): malformed, non-subgroup, low-order, and infinity
    pubkeys are refused ATOMICALLY — none of the import's keys become
    gatherable — on the replicated placement and on every mesh width."""

    def _honest(self, n, start=0):
        cache = ValidatorPubkeyCache(_registry_state(start + n))
        return [cache.get(i) for i in range(start, start + n)]

    def _bad_keys(self):
        from lighthouse_tpu.crypto.bls import adversary as A
        from lighthouse_tpu.crypto.bls import curve_ref as C
        from lighthouse_tpu.crypto.bls.api import BlsError, PublicKey
        from lighthouse_tpu.crypto.bls.fields_ref import Fp

        honest = self._honest(1)[0]
        return BlsError, {
            "non-subgroup": PublicKey(A.non_subgroup_g1_point()),
            "low-order-component": PublicKey(
                honest.point + A.low_order_g1_point()
            ),
            "infinity": PublicKey(C.Point(Fp.zero(), Fp.zero(), True)),
            "malformed": object(),  # no .point at all
        }

    @pytest.mark.parametrize(
        "kind",
        ["non-subgroup", "low-order-component", "infinity", "malformed"],
    )
    def test_import_refused_atomically_replicated(self, kind, monkeypatch):
        monkeypatch.setenv("LIGHTHOUSE_TPU_SHARD_TABLE", "0")
        BlsError, bad = self._bad_keys()
        table = jax_tpu.PubkeyTable()
        batch = self._honest(4) + [bad[kind]] + self._honest(2, start=4)
        with pytest.raises(BlsError, match="key_validate"):
            table.import_new_pubkeys(batch)
        assert len(table) == 0  # nothing from the batch became gatherable
        assert not table.sharded

    @pytest.mark.parametrize("mesh", [1, 2, 4])
    def test_import_refused_on_every_mesh_width(self, mesh, monkeypatch):
        import jax

        from lighthouse_tpu.parallel import verify_sharded as vs

        monkeypatch.setattr(
            vs, "pow2_device_prefix",
            lambda devices=None: list(jax.devices())[:mesh],
        )
        BlsError, bad = self._bad_keys()
        table = jax_tpu.PubkeyTable()
        # enough rows that the mesh-width placements actually shard
        table.import_new_pubkeys(self._honest(32))
        assert table.sharded == (mesh > 1)
        with pytest.raises(BlsError, match="key_validate"):
            table.import_new_pubkeys([bad["low-order-component"]])
        assert len(table) == 32
        # the refusal left the surviving table fully functional
        rows = np.asarray(table.gather(np.arange(3)))
        expect = np.stack(
            [jax_tpu._pk_limbs(pk) for pk in self._honest(3)]
        )
        assert (rows == expect).all()

    def test_key_validate_flag_is_the_planted_weakness(self, monkeypatch):
        """LIGHTHOUSE_TPU_KEY_VALIDATE=0 reopens the seam: a low-order
        key imports and becomes gatherable by validator index — the
        pre-hardening behavior the default-on gate exists to close."""
        from lighthouse_tpu.crypto.bls import adversary as A
        from lighthouse_tpu.crypto.bls.api import PublicKey

        monkeypatch.setenv("LIGHTHOUSE_TPU_KEY_VALIDATE", "0")
        table = jax_tpu.PubkeyTable()
        poisoned = PublicKey(
            self._honest(1)[0].point + A.low_order_g1_point()
        )
        table.import_new_pubkeys([poisoned])
        assert len(table) == 1  # weakness demonstrated: key is resident


def test_import_new_pubkeys_extends_table():
    state = _registry_state(4)
    cache = ValidatorPubkeyCache(state)
    assert len(cache) == 4
    cache.device_table()
    state.validators.append(
        SimpleNamespace(pubkey=interop_keypair(4)[1].to_bytes())
    )
    assert cache.import_new_pubkeys(state) == 1
    assert cache.get(4).validator_index == 4
    assert int(cache.device_table().shape[0]) >= 5
    # idempotent
    assert cache.import_new_pubkeys(state) == 0
