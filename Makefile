# Convenience targets; CI (.github/workflows/ci.yml) runs the same two.

PY ?= python

.PHONY: lint lint-changed lint-baseline test test-lint test-chaos \
	test-crash test-scenario test-serving test-speculate test-kernels \
	test-fuzz fuzz test-adversary fuzz-adversary bench-serving \
	bench-speculate bench-latency bench-scale test-sharded test-multichip \
	warm-compile ledger-report

## lint: per-file + interprocedural project pass (tools/lint, stdlib-only);
## times itself and fails over the 10s budget so it never becomes a
## pre-commit tax
lint:
	$(PY) -m tools.lint --project --budget-s 10

## lint-changed: pre-commit fast path -- only files git says changed
lint-changed:
	$(PY) -m tools.lint --project --changed-only --budget-s 10

## lint-baseline: regenerate the ratchet file after burning down debt
lint-baseline:
	$(PY) -m tools.lint --project --write-baseline

## test: tier-1 suite (CPU, excludes slow/TPU-only)
test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider \
		-p no:xdist -p no:randomly

## test-lint: just the linter's own fixture suite
test-lint:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_lint.py -q \
		-p no:cacheprovider

## test-chaos: deterministic fault-injection suite (the CI chaos job)
test-chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_resilience.py -q \
		-m chaos -p no:cacheprovider

## test-crash: crash-injection matrix + WAL recovery + fsck (the CI crash job)
test-crash:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_crash_safety.py -q \
		-p no:cacheprovider

## test-scenario: full adversarial scenario matrix incl. the combined
## plans, Byzantine validator clients, serving-under-chaos, wire
## transport, and slow scale runs (the CI scenario job; tier-1 keeps
## only the small seeded scenarios)
test-scenario:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_scenarios.py \
		tests/test_byzantine_vc.py -q -m scenario -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_scenarios.py -q \
		-m wire -p no:cacheprovider

## test-fuzz: fuzzing machinery unit tests + tier-1 replay of the pinned
## corpus reproducers under their recorded plants
test-fuzz:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_fuzz.py -q \
		-p no:cacheprovider

## fuzz: a budgeted seeded fuzz window (the CI fuzz job); exit code is
## the number of findings, minimized reproducers land in fuzz-findings/
fuzz:
	JAX_PLATFORMS=cpu $(PY) -m tools.fuzz_cli --start-seed 0 \
		--iterations 12 --budget-s 1200 --corpus-dir fuzz-findings

## test-adversary: aggregation-soundness suite IN FULL — all five probe
## families through the five-path differential rejection matrix (cpu
## oracle, jax per-set, jax aggregated, mesh grouped, fallback
## mid-trip), planted weaknesses, import seams (the CI adversary job;
## tier-1 keeps the fast cpu-oracle subset)
test-adversary:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_bls_adversary.py -q \
		-p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_pubkey_table.py -q \
		-p no:cacheprovider

## fuzz-adversary: budgeted fuzz window with the adversary grammar —
## every generated plan carries aggregation-soundness probes audited
## against the real cpu oracle at scenario end
fuzz-adversary:
	JAX_PLATFORMS=cpu $(PY) -m tools.fuzz_cli --start-seed 100 \
		--iterations 8 --budget-s 1200 --grammar adversary \
		--corpus-dir fuzz-findings

## test-serving: serving-tier suite (cache, SSE fan-out, admission)
test-serving:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_serving.py -q \
		-p no:cacheprovider

## test-speculate: duty-driven precompute & speculative verification —
## the forgery/property suite plus the storm scenario with speculation
## attached (the CI speculate job)
test-speculate:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_speculation.py -q \
		-p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_scenarios.py -q \
		-m speculate -p no:cacheprovider

## test-kernels: full Pallas kernel parity matrix incl. the slow fused
## tower/Miller kernels in interpret mode (the CI kernels job)
test-kernels:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_pallas_kernels.py -q \
		-p no:cacheprovider

## bench-serving: cached-vs-uncached requests/s (the CI serving job)
bench-serving:
	JAX_PLATFORMS=cpu $(PY) bench.py --serving --out bench-serving.json

## bench-speculate: critical-path aggregate sets/s with the precompute
## off / on / on+speculation, plus hit/correction/miss ratios (one JSON
## line on stdout — the artifact)
bench-speculate:
	BENCH_PLATFORM=cpu JAX_PLATFORMS=cpu $(PY) bench.py --speculate \
		| tee bench-speculate.json

## bench-latency: bursty-arrival per-lane time-to-verdict p50/p95
## through the continuous-batching scheduler vs the whole-batch
## baseline, plus the pad-waste ratio (one JSON line — the artifact)
bench-latency:
	BENCH_PLATFORM=cpu JAX_PLATFORMS=cpu $(PY) bench.py --latency \
		| tee bench-latency.json

## ledger-report: run the latency bench, then print the launch-ledger
## occupancy / pad-waste / compile-tax table (+ per-lane p50/p95
## time-to-verdict) from its artifact — the same renderer as
## `cli ledger --report` and /lighthouse/ledger/report
ledger-report: bench-latency
	JAX_PLATFORMS=cpu $(PY) -m tools.ledger_report bench-latency.json

## bench-scale: 2M-validator epoch transition on the simulated 4-device
## mesh + sharded pubkey-table per-device bytes (one JSON line — the
## artifact the CI sharded-state job uploads)
bench-scale:
	BENCH_PLATFORM=cpu JAX_PLATFORMS=cpu $(PY) bench.py --scale \
		| tee bench-scale.json

## test-sharded: the sharded-state differential matrix on a forced
## 4-device mesh (the CI sharded-state job; in-suite tier-1 runs the
## same file on the conftest 8-device mesh minus the slow chip-fault
## test, which compiles the full verify_jit program)
test-sharded:
	JAX_PLATFORMS=cpu \
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	$(PY) -m pytest tests/test_sharded_state.py -q -p no:cacheprovider

## test-multichip: the sharded verifier against the single-device kernel
## on the 8-device virtual mesh, slow parity tests included (each
## compiles the whole verify_body; tier-1 runs only the fast twins). One
## process a slow class, as XLA:CPU has aborted after about six of these
## compiles in one process; a first compile takes up to 15 minutes, over
## the suite's per-test time limit
MULTICHIP_SLOW = TestShardedMatchesSingleDevice TestGeneratorPairCountedOnce \
	TestMeshVerifierRealKernel TestGroupedMeshReduction
test-multichip:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_multichip.py -q \
		-m 'not slow' -p no:cacheprovider
	rc=0; for c in $(MULTICHIP_SLOW); do \
		JAX_PLATFORMS=cpu LIGHTHOUSE_TPU_TEST_TIMEOUT_S=1800 \
		$(PY) -m pytest "tests/test_multichip.py::$$c" -q -m slow \
			-p no:cacheprovider || rc=1; \
	done; exit $$rc

## warm-compile: AOT-compile every verifier shape bucket into the
## persistent compile cache (deploy-time warm pass; `cli warm`)
warm-compile:
	$(PY) -m lighthouse_tpu.cli warm
