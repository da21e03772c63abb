# Developer entry points. Tests and the host-side overhead checks run on
# the CPU (CPU_ENV; tests/conftest.py forces it too). `make chip-smoke`
# needs a TPU and fails without one; from a machine with no chip, send it
# through the chip tool (`chiprun -- python3 chip_smoke.py`). The device
# benchmark is kvbench/run.py (BENCHMARK.json).

PY := python
CPU_ENV := PYTHONPATH=. JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: test unit-test-race tsan asan native chip-smoke bench-hotpath bench-hotpath-fleet bench-hotpath-evict bench-engine-telemetry bench-shard bench-fleet bench-pyprof bench-workingset bench-controller bench-graytail bench-fencing bench-incident perf-check verify graft-check verify-examples chaos lint clean

test: native
	$(CPU_ENV) $(PY) -m pytest tests/ -q

# Fault-injection suite (resilience layer): fixed failpoint seed so a
# chaos failure reproduces byte-for-byte on a rerun. KVTPU_LOCKDEP arms
# the runtime lock-order witness (utils/lockdep.py) — chaos schedules are
# exactly where latent A/B lock inversions surface.
chaos: native
	$(CPU_ENV) KVTPU_FAILPOINT_SEED=1337 KVTPU_LOCKDEP=1 \
	  $(PY) -m pytest tests/ -q -m chaos

# Unified lint driver (hack/kvlint.py): resilience (RES-*, swallowed
# errors / non-atomic persistence), observability (OBS-*, span+metric
# namespaces and docs coverage), and concurrency (CONC-*, lock re-entry,
# lock-order cycles, blocking calls and escaping callbacks under locks —
# llmd_kv_cache_tpu/tools/conclint). One `path:line: RULE message`
# format; `--json` for machines.
lint:
	$(PY) hack/kvlint.py llmd_kv_cache_tpu

# Concurrency-focused pass (the reference runs `go test -race` nightly;
# Python has no race detector, so the thread-heavy suites are repeated —
# any single failure fails the target, surfacing flaky races instead of
# hiding them). KVTPU_LOCKDEP=1 swaps every library lock for the lockdep
# witness: the first observed lock-order cycle or illegal re-entry
# raises instead of deadlocking one run in a thousand.
unit-test-race: native tsan
	for i in 1 2 3; do \
	  $(CPU_ENV) KVTPU_LOCKDEP=1 $(PY) -m pytest tests/test_stress.py \
	    tests/test_pool.py tests/test_index.py \
	    tests/test_zmq_integration.py tests/test_evictor.py -q || exit 1; \
	done

# Native race tier: the GIL hides C++ data races from the pytest reruns,
# so the kvio pool and the kvindex engine get hammered under
# ThreadSanitizer directly (go test -race parity for the native side).
tsan:
	$(MAKE) -s -C csrc/kvio tsan
	$(MAKE) -s -C csrc/kvindex tsan

# Native memory tier: ASan+UBSan over the same test binaries — heap
# misuse and UB that TSAN's race instrumentation does not see.
asan:
	$(MAKE) -s -C csrc/kvio asan
	$(MAKE) -s -C csrc/kvindex asan

native:
	$(MAKE) -s -C csrc/kvio
	$(MAKE) -s -C csrc/kvindex

# The quickest proof that the routed serving path still starts on the
# chip (it rebuilds the native libraries itself).
chip-smoke:
	$(PY) chip_smoke.py

# Score/ingest hot-path microbenchmark (prefix cache, early-exit lookup,
# batched ingestion) — pure CPU scheduling-path work, so it pins the CPU
# backend.
bench-hotpath: native
	$(CPU_ENV) $(PY) hack/bench_hotpath.py

# Fleet-scale data-plane arm (ISSUE 17): batched LookupBlocksBatch
# fan-out vs the per-chunk wire over a 4-shard in-process fleet with
# concurrent zero-copy ingest; hard-asserts the >=5x throughput ratio
# and the ingest-lag staleness bound internally.
bench-hotpath-fleet: native
	$(CPU_ENV) $(PY) hack/bench_hotpath.py --fleet

# Eviction arm: the pages one admission takes from a full pool (45 of
# 2,559 cached blocks), through BlockManager's kept order and through the
# scan it replaced. Host time; the ratio is the sentinel's value.
bench-hotpath-evict: native
	$(CPU_ENV) $(PY) hack/bench_hotpath.py --evict

# Engine-telemetry overhead gate: asserts the per-step hook cost stays
# under 1% of the decode-step p50 (telemetry/engine_telemetry.py).
bench-engine-telemetry: native
	$(CPU_ENV) $(PY) bench.py --engine-telemetry

# Sharded control-plane gate (cluster/): scatter-gather score p99 over a
# 4-shard gRPC fleet at 4x aggregate index size must stay within 1.15x of
# the single-shard baseline (bench_shard_fanout).
bench-shard: native
	$(CPU_ENV) $(PY) bench.py --shards 4

# Fleet-telemetry overhead gate (telemetry/ + services/telemetry_
# collector): per-span export cost (identity stamp + seq + ring append)
# must stay under 1% of the Python-path score p50; also reports
# /debug/spans pull and trace-assembly round timings.
bench-fleet: native
	$(CPU_ENV) $(PY) bench.py --fleet-telemetry

# Continuous-profiling overhead gate (telemetry/sampling_profiler): the
# always-on sampler's pass-cost x hz CPU fraction must stay under 1% of
# the score p50; also emits the hot-function shares the perf sentinel
# diffs.
bench-pyprof: native
	$(CPU_ENV) $(PY) bench.py --pyprof-overhead

# Working-set analytics gates (telemetry/workingset): the SHARDS-sampled
# miss-ratio curve must track an exact LRU-simulation oracle within a
# bounded error, and the per-score hook cost must stay under 1% of the
# score p50.
bench-workingset: native
	$(CPU_ENV) $(PY) bench.py --workingset

# Fleet-controller chaos arm (control/): traffic-flip re-role, 4x index
# ramp shard scale-up, and flap injection against a modeled fleet; the
# flap-injection executed-action count is the perf-sentinel value
# (hysteresis must bound it).
bench-controller: native
	$(CPU_ENV) $(PY) bench.py --controller

# Gray-failure tail-tolerance gate (resilience/cluster, PR 16): one of
# four shards delayed 10x via a seeded delay failpoint — hedged fan-out
# must hold the score p99 within 2x of the interleaved healthy baseline
# (and under half the injected delay), breakers must stay closed, every
# deadline overrun must be shed or flagged degraded, and the healthy-path
# hedging bookkeeping must cost < 1% of the score p50 (the perf-sentinel
# value).
bench-graytail: native
	$(CPU_ENV) $(PY) bench.py --graytail

# Ground-truth audit plane gate (telemetry/audit.py): the per-score
# prediction hook must cost < 1% of the Python-path score p50 (the
# perf-sentinel value); the once-per-request outcome append is reported
# informationally.
bench-audit: native
	$(CPU_ENV) $(PY) bench.py --audit

# Epoch-fencing gate (cluster/membership.py): the per-score fence check
# (MembershipTable.check_request) must cost < 1% of the score p50 — the
# fencing plane rides the hot path on every request, so its clean path
# has to be a lock-free cached-decision compare.
bench-fencing: native
	$(CPU_ENV) $(PY) bench.py --fencing

# Incident black-box gate (telemetry/incident.py): the alert-edge
# trigger hook (IncidentManager.maybe_open) must cost < 1% of the score
# p50 — the evidence fan-out and the bundle write run on a detached
# worker, and the bench proves the accepted edge never pays them.
bench-incident: native
	$(CPU_ENV) $(PY) bench.py --incident

# Perf-regression sentinel: run the profiling + working-set gates and the
# controller chaos arm, then diff their values and hot-function shares
# against the committed baseline manifest. Emits machine-verdict
# `PERF PASS|FAIL ...` lines; fails on regression.
perf-check: native
	$(CPU_ENV) $(PY) bench.py --pyprof-overhead > /tmp/kvtpu_pyprof_bench.json
	$(CPU_ENV) $(PY) bench.py --workingset > /tmp/kvtpu_workingset_bench.json
	$(CPU_ENV) $(PY) bench.py --controller > /tmp/kvtpu_controller_bench.json
	$(CPU_ENV) $(PY) bench.py --graytail > /tmp/kvtpu_graytail_bench.json
	$(CPU_ENV) $(PY) bench.py --audit > /tmp/kvtpu_audit_bench.json
	$(CPU_ENV) $(PY) bench.py --fencing > /tmp/kvtpu_fencing_bench.json
	$(CPU_ENV) $(PY) bench.py --incident > /tmp/kvtpu_incident_bench.json
	$(CPU_ENV) $(PY) hack/bench_hotpath.py --fleet > /tmp/kvtpu_fleet_bench.json
	$(CPU_ENV) $(PY) hack/bench_hotpath.py --evict > /tmp/kvtpu_evict_bench.json
	$(PY) hack/perf_sentinel.py --baseline benchmarking/perf_baseline.json \
	  --results pyprof-overhead=/tmp/kvtpu_pyprof_bench.json \
	  --results workingset=/tmp/kvtpu_workingset_bench.json \
	  --results controller=/tmp/kvtpu_controller_bench.json \
	  --results graytail=/tmp/kvtpu_graytail_bench.json \
	  --results audit=/tmp/kvtpu_audit_bench.json \
	  --results fencing=/tmp/kvtpu_fencing_bench.json \
	  --results incident=/tmp/kvtpu_incident_bench.json \
	  --results hotpath-fleet=/tmp/kvtpu_fleet_bench.json \
	  --results hotpath-evict=/tmp/kvtpu_evict_bench.json

# The pre-merge bundle: conventions lint + the perf sentinel.
verify: lint perf-check

# Run every runnable example headlessly (the reference's
# hack/verify-examples.sh equivalent).
verify-examples: native
	$(CPU_ENV) $(PY) examples/offline_events.py
	$(CPU_ENV) $(PY) examples/fleet_demo.py
	$(CPU_ENV) $(PY) examples/tp_serving_demo.py
	$(CPU_ENV) $(PY) examples/long_context_sp.py
	$(CPU_ENV) $(PY) examples/serve_hf_checkpoint.py
	$(CPU_ENV) $(PY) examples/redis_indexer.py
	$(CPU_ENV) $(PY) examples/fp8_kv_serving.py
	$(CPU_ENV) $(PY) examples/sharded_cluster_demo.py

# Developer check on the CPU backend.
graft-check:
	$(CPU_ENV) $(PY) -c "import __graft_entry__, jax; fn, a = __graft_entry__.entry(); \
	  print(jax.jit(fn)(*a).shape)"
	$(CPU_ENV) $(PY) -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"

clean:
	$(MAKE) -C csrc/kvio clean
	$(MAKE) -C csrc/kvindex clean
	rm -rf .jax_cache chiprun_out
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
