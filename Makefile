# NornicDB-TPU (ref: the reference's Makefile test/build targets)

.PHONY: test test-fast lint lint-baseline sanitize jitgate smoke capacity-report chaos soak soak-ci soak-nornsan soak-multiworker bench-search bench-embed bench-workers bench-cypher native e2e-bench clean

test:
	python -m pytest tests/ -q

lint:
	python -m nornicdb_tpu.tools.nornlint nornicdb_tpu --baseline tools/nornlint_baseline.json

lint-baseline:
	python -m nornicdb_tpu.tools.nornlint nornicdb_tpu --baseline tools/nornlint_baseline.json --update-baseline

# runtime lock sanitizer over the threaded suites (docs/linting.md#nornsan)
sanitize:
	NORNSAN=1 python -m pytest tests/test_concurrency.py tests/test_replication.py tests/test_replication_scenarios.py tests/test_nornsan.py tests/test_adjacency.py tests/test_telemetry.py tests/test_backend.py tests/test_sharded_serving.py tests/test_int8_residency.py tests/test_ivf_tuner.py tests/test_serving.py tests/test_genserve.py tests/test_broker.py tests/test_shm_readplane.py tests/test_workers.py tests/test_columnar.py tests/test_fleet_telemetry.py -q -m 'not slow'

# runtime recompile sentinel over the serving suites: every fresh XLA
# compile is attributed to a (subsystem, kind, shape) key and any test
# that compiles after its declared warmup fails (docs/linting.md#nornjit)
jitgate:
	NORNJIT=1 python -m pytest tests/test_serving.py tests/test_genserve.py tests/test_sharded_serving.py tests/test_nornjit.py tests/test_columnar.py -q -m 'not slow'

# search/embed suite with the accelerator backend forced to hang: the
# lifecycle manager must keep the stack serving from CPU (docs/backend.md)
chaos:
	NORNICDB_FAKE_BACKEND=hang NORNICDB_DEVICE_ACQUIRE_TIMEOUT=2 python -m pytest tests/test_embed_search.py tests/test_search_unit_depth.py tests/test_sharded_serving.py tests/test_int8_residency.py tests/test_ivf_tuner.py tests/test_serving.py tests/test_genserve.py tests/test_broker.py tests/test_shm_readplane.py tests/test_workers.py tests/test_columnar.py -q -m 'not slow'

# live-server /metrics + /admin/traces smoke (docs/observability.md)
smoke:
	python scripts/telemetry_smoke.py

# live-server /admin/capacity cost-table report: per-program EWMA costs,
# headroom (max sustainable qps), SLO window state (docs/capacity.md)
capacity-report:
	python scripts/capacity_report.py

# 5-minute chaos/load soak: mixed Bolt/HTTP/gRPC/Qdrant traffic under
# composed replication+backend+storage fault injection, telemetry-backed
# invariants, SOAK_report.json artifact (docs/chaos.md)
soak:
	python -m nornicdb_tpu.soak --scenario full --report SOAK_report.json

# ~60 s seeded CI soak profile (gating; same fault planes, compressed)
soak-ci:
	python -m nornicdb_tpu.soak --scenario ci --report SOAK_report_ci.json

# CI soak under the runtime lock sanitizer (docs/linting.md#nornsan);
# skips the multiworker phase (covered by the plain soak-ci run)
soak-nornsan:
	NORNSAN=1 python -m nornicdb_tpu.soak --scenario ci --no-multiworker --report SOAK_report_ci.json

# multi-process serving soak: prefork worker pool under mixed traffic
# with worker kills + backend hang (respawn / broker-reconnect /
# shared-memory fallback invariants; docs/operations.md)
soak-multiworker:
	python -m nornicdb_tpu.soak --scenario multiworker --report SOAK_report_multiworker.json

test-fast:
	python -m pytest tests/ -q -x

# passthrough: `make bench-search ROWS=10000000 DIMS=64 MODE=exact,ivf
# BACKENDS=sharded_int8` regenerates the artifact at any scale; the
# committed BENCH_search.json carries a 10M-row int8-resident run plus
# the trajectory sizes. Exit invariants include the recall floor and the
# int8 exact-rescore bit-match (docs/operations.md "Recall tuning").
bench-search:
	python scripts/bench_search.py $(if $(ROWS),--rows $(ROWS)) $(if $(DIMS),--dims $(DIMS)) $(if $(MODE),--mode $(MODE)) $(if $(BACKENDS),--backends $(BACKENDS)) $(BENCH_SEARCH_ARGS)

# ragged-packed vs padded fixed-batch embedding throughput at mixed text
# lengths (writes BENCH_embed.json; asserts the one-program-per-packed-
# batch invariant at exit)
bench-embed:
	python scripts/bench_embed.py

# 1/2/4/8-worker prefork scaling sweep under mixed search+embed+Cypher
# load (writes BENCH_multiproc.json; asserts the one-program-per-fused-
# batch invariant and the 4-worker >= 2x scaling floor at exit)
bench-workers:
	python scripts/bench_workers.py

# columnar Cypher pipeline vs the row-at-a-time interpreter at 100k
# nodes / 500k edges (writes BENCH_cypher.json; exit invariants: zero
# fresh compiles + zero all_edges() rescans in the timed pass, >=3x p50
# on two shapes — docs/operations.md "Columnar Cypher execution")
bench-cypher:
	python scripts/bench_cypher.py $(BENCH_CYPHER_ARGS)

e2e-bench:
	python benchmarks/endpoints_bench.py

native:
	$(MAKE) -C native

graft-check:
	python __graft_entry__.py

clean:
	$(MAKE) -C native clean
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null; true
