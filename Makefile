# Repo-level entry points. `make lint` is the pre-merge gate: the
# rtlint static pass over the default target set (ray_tpu/, tools/,
# bench_*.py — against the committed baseline) plus the native store's
# sanitizer stress tests.

PY ?= python
LINT_JOBS ?= 4

.PHONY: lint rtlint lint-stats lint-changed lint-fix sanitizers test \
  fast-test \
  bench-data bench-obs bench-scale bench-serve-obs bench-serve-ft \
  bench-collective bench-multitenant bench-serve-macro \
  bench-rollup

lint: rtlint sanitizers

# The gate also drops a SARIF artifact for code-scanning upload.
RTLINT_SARIF ?= rtlint.sarif
rtlint:
	$(PY) -m tools.rtlint --jobs $(LINT_JOBS) --sarif-out $(RTLINT_SARIF)

# Apply the mechanical autofixes (RT004 ref leash, RT013 boundary
# tuple-freeze) in place, then report what is left for a human.
lint-fix:
	$(PY) -m tools.rtlint --jobs $(LINT_JOBS) --fix

# Per-rule found/suppressed/baselined counts over the default targets;
# MIGRATION.md pins these via tools/check_claims.py.
lint-stats:
	$(PY) -m tools.rtlint --jobs $(LINT_JOBS) --stats

# Lint only files changed vs HEAD (plus untracked) — the fast
# inner-loop variant of the gate.
lint-changed:
	$(PY) -m tools.rtlint --changed

# Regenerates BENCH_DATA.json (data->device feed probes); run
# tools/check_claims.py afterwards — MIGRATION.md pins these numbers.
bench-data:
	JAX_PLATFORMS=cpu $(PY) bench_data.py

# Regenerates BENCH_OBS.json (flight-recorder overhead probes); run
# tools/check_claims.py afterwards — MIGRATION.md pins these numbers.
bench-obs:
	JAX_PLATFORMS=cpu $(PY) bench_obs.py

# Appends one bench_rollup trajectory record (every BENCH_*.json gate
# headline) to PROGRESS.jsonl.
bench-rollup:
	$(PY) bench.py --rollup

# Regenerates BENCH_SCALE.json (scalability envelope + control-plane
# profiler decomposition); run tools/check_claims.py afterwards —
# MIGRATION.md pins these numbers.
bench-scale:
	JAX_PLATFORMS=cpu $(PY) bench_scale.py

# Regenerates BENCH_SERVE_OBS.json (request-observatory overhead +
# phase-coverage + HOL probes); run tools/check_claims.py afterwards —
# MIGRATION.md pins these numbers.
bench-serve-obs:
	JAX_PLATFORMS=cpu $(PY) bench_serve_obs.py

# Regenerates BENCH_SERVE_FT.json (survival-plane probes: chaos TTFT,
# shed latency, drain, controller failover); run tools/check_claims.py
# afterwards — MIGRATION.md pins these numbers.
bench-serve-ft:
	JAX_PLATFORMS=cpu $(PY) bench_serve_ft.py

# Regenerates BENCH_MULTITENANT.json (priority preemption: graceful
# reclamation, chip return, three-tenant SLO accounting, hard-kill
# deadline under mid-drain chaos); the bench asserts its own gates. Run
# tools/check_claims.py afterwards — MIGRATION.md pins these numbers.
bench-multitenant:
	JAX_PLATFORMS=cpu $(PY) bench_multitenant.py

# Regenerates BENCH_COLLECTIVE.json (topology-native collectives:
# algorithm selection, sharded-hier DCN bytes, quantized wire); the
# bench asserts its own gates. Run tools/check_claims.py afterwards —
# MIGRATION.md pins these numbers.
bench-collective:
	JAX_PLATFORMS=cpu $(PY) bench_collective.py

# Regenerates BENCH_SERVE_MACRO.json (the cluster witness: trace
# record/replay byte identity, sustained-QPS client<->server latency
# reconciliation, chaos replay with autoscaler tracking); the bench
# asserts its own gates. Run tools/check_claims.py afterwards —
# MIGRATION.md pins these numbers.
bench-serve-macro:
	JAX_PLATFORMS=cpu $(PY) bench_serve_macro.py

sanitizers:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_native_sanitizers.py \
	  -q -m sanitizer -p no:cacheprovider

fast-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m "not slow" \
	  -p no:cacheprovider

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -p no:cacheprovider
