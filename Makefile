# Repo-level entry points. `make lint` is the pre-merge gate: the
# rtlint static pass over the default target set (ray_tpu/, tools/ —
# against the committed baseline) plus the native store's sanitizer
# stress tests.

PY ?= python
LINT_JOBS ?= 4

.PHONY: lint rtlint lint-stats lint-changed lint-fix sanitizers test \
  fast-test

lint: rtlint sanitizers

# The gate also drops a SARIF artifact for code-scanning upload.
RTLINT_SARIF ?= rtlint.sarif
rtlint:
	$(PY) -m tools.rtlint --jobs $(LINT_JOBS) --sarif-out $(RTLINT_SARIF)

# Apply the mechanical autofixes (RT004 ref leash, RT013 boundary
# tuple-freeze) in place, then report what is left for a human.
lint-fix:
	$(PY) -m tools.rtlint --jobs $(LINT_JOBS) --fix

# Per-rule found/suppressed/baselined counts over the default targets.
lint-stats:
	$(PY) -m tools.rtlint --jobs $(LINT_JOBS) --stats

# Lint only files changed vs HEAD (plus untracked) — the fast
# inner-loop variant of the gate.
lint-changed:
	$(PY) -m tools.rtlint --changed

sanitizers:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_native_sanitizers.py \
	  -q -m sanitizer -p no:cacheprovider

fast-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m "not slow" \
	  -p no:cacheprovider

test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -p no:cacheprovider
