"""A whole run past the look for a chip, on the program's pure-Python
backend at a tiny size: sound, it is correct; with the control or any
planted fault under the timed path, the comparison refuses it. The
exchange between chips is a fault no one-chip cell can have."""

import json
from pathlib import Path

import pytest

from benchmark import control, run, spec

DATA = Path(__file__).parent / "data"
SECONDS = 9.0  # three or more tiny batches: both forged twins land in it


@pytest.fixture(scope="module")
def cpu_backend():
    from lighthouse_tpu.crypto.bls import api

    saved = api._BACKEND_NAME
    api.set_backend("cpu")
    yield
    if saved is not None:
        api.set_backend(saved)


def _cell(traffic: str) -> spec.Cell:
    s = spec.load_spec()
    config = json.loads((DATA / "tiny.json").read_text())
    mix = json.loads((spec.PACKAGE / "traffic" / f"{traffic}.json").read_text())
    mix["forged_every"] = 2
    return spec.Cell(f"tiny.{traffic}", 1, config, mix, s["end_to_end"], [])


def _run(traffic: str):
    import jax

    result = run.run_cell(
        _cell(traffic), 2**31 + 99, SECONDS, False, jax.devices()[:1]
    )
    return result


TRAFFIC = ["block-import", "gossip-attestations"]


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_a_sound_run_is_correct(cpu_backend, traffic):
    result = _run(traffic)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"sets_per_s", "verdict_p95_ms", "setup_s"}
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
@pytest.mark.parametrize("traffic", TRAFFIC)
def test_a_planted_fault_is_refused(cpu_backend, traffic, fault):
    with control.planted(fault):
        result = _run(traffic)
    assert result["correct"] is False
    assert result["checks"]["verdict_mismatches"]["value"] > 0
    assert result["failed"] > 0
