"""A cell made of new files only, and a `workloads` entry, is found by
name and generates its traffic: the harness needs no edit."""

import json

from benchmark import generator, reference, spec


def _write(path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj))


def test_a_cell_of_new_files_loads_and_generates(tmp_path):
    config = {
        "source": "a made-up deployment", "reduced": [],
        "validator_rows": 32, "distinct_keys": 4, "committee": 3,
        "committees": 3,
    }
    traffic = {
        "entry": "async", "in_flight": 2, "pool_batches": 3, "slots": 2,
        "forged_every": 4,
        "groups": [
            {"name": "agg", "sets": 4, "keys": "committee",
             "messages": "committees", "draw": "uniform"},
            {"name": "one", "sets": 1, "keys": 1, "messages": 1,
             "draw": "cycle"},
        ],
    }
    _write(tmp_path / "benchmark" / "configs" / "made-up.json", config)
    _write(tmp_path / "benchmark" / "traffic" / "new-mix.json", traffic)
    metric = {"name": "sets_per_s", "unit": "sets/s", "better": "higher",
              "bound": 0.05, "source": "host_clock"}
    _write(tmp_path / "BENCHMARK.json", {
        "configs": [{"name": "made-up", "file": "benchmark/configs/made-up.json"}],
        "workloads": [{"name": "made-up.new-mix", "config": "made-up",
                       "traffic": "new-mix", "chips": 1}],
        "end_to_end": [metric],
        "per_layer": [],
    })

    cell = spec.resolve(spec.load_spec(tmp_path), "made-up.new-mix", tmp_path)
    assert cell.config == config and cell.traffic == traffic
    assert [m["name"] for m in cell.end_to_end] == ["sets_per_s"]

    pool = generator.build_pool(cell.config, cell.traffic, seed=2**31 + 5)
    assert len(pool.batches) == 3
    assert all(len(b.sets) == 5 for b in pool.batches)
    assert all(len(s.rows) == 3 for b in pool.batches for s in b.sets[:4])
    ref = reference.Reference(pool)
    reference.check_registry(pool)
    for i, b in enumerate(pool.batches):
        assert ref.batch_valid(generator.batch_sets(pool, i, None))
        for twin in (0, 1):
            assert not ref.batch_valid(generator.batch_sets(pool, i, twin))
    assert [generator.submission(traffic, pool, k) for k in range(9)] == [
        (0, 0), (1, None), (2, None), (0, None), (1, 1), (2, None),
        (0, None), (1, None), (2, 0),
    ]


def test_the_same_seed_gives_the_same_pool():
    config = {"validator_rows": 16, "distinct_keys": 4}
    traffic = {"pool_batches": 2, "slots": 1, "groups": [
        {"name": "g", "sets": 2, "keys": 2, "messages": 2, "draw": "uniform"}]}
    a = generator.build_pool(config, traffic, 77)
    assert a == generator.build_pool(config, traffic, 77)
    assert a != generator.build_pool(config, traffic, 78)


def test_every_named_file_of_the_benchmark_exists():
    s = spec.load_spec()
    for w in s["workloads"]:
        cell = spec.resolve(s, w["name"])
        assert cell.chips in (1, 4)
        for m in cell.per_layer:
            assert callable(spec.reader(m["name"]))
    assert spec.device_entry("TPU v5 lite")["hbm_bytes"] == 16_000_000_000
