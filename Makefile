# Developer entry points. The test environment pins jax to the CPU backend
# with 8 virtual devices (tests/conftest.py); the benchmark (BENCHMARK.json,
# benchmark/run.py) runs on the real TPU chip.

PY ?= python
PYTEST = $(PY) -m pytest

# graft-lint: the project-wide static analysis suite (docs/static-
# analysis.md) — host-sync leaks, lock-order cycles/inversions/blocking-
# under-lock, conf-key drift + startup_only scope, cancel-beat coverage,
# the metric-catalog check, and the ISSUE-15 flow passes
# (resource-lifecycle: must-release-on-all-paths over per-function CFGs;
# guarded-by: lock/attribute consistency from annotations + majority
# inference). Also runs inside tier-1 via tests/test_analysis.py so
# `make check`/CI cannot skip it.
#
# Exit codes: 0 = clean (every finding suppressed or baselined);
#             1 = live findings or framework errors (malformed markers,
#                 stale/protected baseline rows) — fix, suppress at the
#                 site, or baseline outside exec/serve/sched;
#             2 = usage error (unknown pass id, --write-baseline with a
#                 --passes subset).
# Machine-readable findings for CI annotation: `make lint-json` (same
# exit codes; one JSON doc with pass/path/line/fingerprint/state).
.PHONY: lint
lint:
	JAX_PLATFORMS=cpu $(PY) -m spark_rapids_tpu.analysis .

.PHONY: lint-json
lint-json:
	@JAX_PLATFORMS=cpu $(PY) -m spark_rapids_tpu.analysis . --format json

# Regenerate the lint baseline (spark_rapids_tpu/analysis/BASELINE.lint).
# Every NEW entry needs a justification: make lint-baseline JUSTIFY='why'.
# Entries under exec/, serve/, or sched/ are refused — findings there are
# fixed or suppressed at the site, never baselined.
.PHONY: lint-baseline
lint-baseline:
	JAX_PLATFORMS=cpu $(PY) -m spark_rapids_tpu.analysis . \
	  --write-baseline --justify '$(JUSTIFY)'

# Static metric-catalog drift check — now the graft-lint `metrics` pass;
# this PR-9 entry point stays as a thin standalone shim.
.PHONY: metrics-lint
metrics-lint:
	JAX_PLATFORMS=cpu $(PY) -m spark_rapids_tpu.metrics_lint .

# The pre-snapshot gate: the FULL suite in one command. Red here = do not
# ship (VERDICT r3 weak #3: a red suite must be impossible to snapshot).
.PHONY: check
check: lint
	$(PYTEST) tests/ -q

# The fast core: everything except the heavyweight end-to-end suites —
# for inner-loop development on a small box. Ends with the e2e SMOKE slice
# so the inner loop can never drift far from the e2e truth (VERDICT r4
# weak #4: check-fast used to exclude exactly the suites most likely to
# break).
.PHONY: check-fast
check-fast: lint
	$(PYTEST) tests/ -q \
	  --ignore=tests/test_tpch.py \
	  --ignore=tests/test_tpch_sql.py \
	  --ignore=tests/test_tpcds.py \
	  --ignore=tests/test_qa_generated.py \
	  --ignore=tests/test_multiproc_shuffle.py \
	  --ignore=tests/test_distributed.py \
	  --ignore=tests/test_pallas.py
	$(MAKE) check-e2e-smoke

# A <5 min cross-section of every e2e rig: one TPC-H query, one TPC-DS
# query, ten generated QA cases, one multi-process query, one mesh test.
.PHONY: check-e2e-smoke
check-e2e-smoke:
	$(PYTEST) -q \
	  "tests/test_tpch.py::test_tpch_differential[6]" \
	  "tests/test_tpcds.py::test_tpcds_differential[3]" \
	  "tests/test_multiproc_shuffle.py::test_multiproc_query_over_tcp[agg]" \
	  "tests/test_distributed.py::test_mesh_group_by" \
	  "tests/test_qa_generated.py::test_qa_generated[0]" \
	  "tests/test_qa_generated.py::test_qa_generated[1]" \
	  "tests/test_qa_generated.py::test_qa_generated[2]" \
	  "tests/test_qa_generated.py::test_qa_generated[3]" \
	  "tests/test_qa_generated.py::test_qa_generated[4]" \
	  "tests/test_qa_generated.py::test_qa_generated[5]" \
	  "tests/test_qa_generated.py::test_qa_generated[6]" \
	  "tests/test_qa_generated.py::test_qa_generated[7]" \
	  "tests/test_qa_generated.py::test_qa_generated[8]" \
	  "tests/test_qa_generated.py::test_qa_generated[9]"

# End-to-end rigs only.
.PHONY: check-e2e
check-e2e:
	$(PYTEST) tests/test_tpch.py tests/test_tpch_sql.py tests/test_tpcds.py \
	  tests/test_qa_generated.py \
	  tests/test_multiproc_shuffle.py tests/test_distributed.py -q

# Regenerate the code-generated docs (configs.md, supported_ops.md).
.PHONY: docs
docs:
	$(PY) -c "import jax; jax.config.update('jax_platforms','cpu'); \
	  from spark_rapids_tpu import docs_gen; docs_gen.main('docs')"

# Regenerate the golden corpus fixtures from the independent oracle.
.PHONY: golden
golden:
	$(PY) tests/golden/gen_golden.py

# Start the Arrow-IPC SQL endpoint with the TPC-H demo catalog registered
# as temp views (docs/serving.md). Connect with:
#   python -c "from spark_rapids_tpu.serve import connect; \
#     print(connect(port=8045).sql('select count(*) c from lineitem').to_table())"
SERVE_PORT ?= 8045
SERVE_SF ?= 0.01
.PHONY: serve
serve:
	$(PY) -m spark_rapids_tpu.serve --port $(SERVE_PORT) --tpch-sf $(SERVE_SF)

# Live-analytics chaos suite (ISSUE 20): appender storms against wire
# subscriber fleets with per-epoch bit-identity oracles replayed from the
# delta log, subscribers killed mid-UPDATE train, and injected spill
# faults on maintained-state demotion — degrade to full refresh, never
# corrupt.
.PHONY: chaos-live
chaos-live:
	$(PYTEST) tests/test_chaos_live.py -q -m chaos

# Serve-path chaos suite (ISSUE 7): injected kernel stalls, compile delays,
# slow-loris clients, mid-stream socket drops, corrupt frames — asserts
# bit-identical results, watchdog cancellation, and zero leaked
# permits/threads/fds. The in-process chaos suite rides the same marker.
.PHONY: chaos-serve
chaos-serve:
	$(PYTEST) tests/test_chaos_serve.py -q -m chaos

# Restart/corruption chaos suite (ISSUE 11): boot a server, kill it
# mid-compile, restart against the same compile-cache dir, and drive every
# faults.compileCache.* damage point (truncate, bit flip, stale version
# fence, crash-between-temp-and-rename, wedged lock holder) — asserts
# bit-identical TPC-H results, quarantine+rebuild, and a near-zero
# second-boot compile ledger.
.PHONY: chaos-restart
chaos-restart:
	$(PYTEST) tests/test_chaos_restart.py -q -m chaos

# Recovery chaos suite (ISSUE 18): device-fault + peer-loss storms with
# partition-granular lineage re-execution, straggler speculation under
# concurrent faults, and serve-fleet failover (kill a peer mid-stream,
# dedup-keyed replay, transparent re-prepare) — asserts bit-identical
# results vs the CPU oracle with ZERO whole-query restarts.
.PHONY: chaos-recovery
chaos-recovery:
	$(PYTEST) tests/test_chaos_recovery.py -q -m chaos

# The full chaos surface (in-process + serve-path + restart/corruption +
# recovery + live-analytics).
# Every chaos-marked test runs under BOTH runtime harnesses: lockwatch
# (lock-order races) and reswatch (end-of-test resource balance —
# permits/threads/fds/flocks/spans back to the entry snapshot). Force
# reswatch onto EVERY test with SRT_RESWATCH=1; disable with =0.
.PHONY: chaos
chaos:
	$(PYTEST) -q -m chaos
