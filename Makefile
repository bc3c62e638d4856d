# mpcium_tpu developer entry points (reference Makefile: go install ./cmd/...)

PY ?= python

.PHONY: install lint check shapecheck warmcheck prewarm trace-check test test-all bench broker chaos soak soak-tests setup-identities setup-initiator clean

install:
	pip install -e . --no-build-isolation --no-deps

# static analysis (STATIC_ANALYSIS.md): ruff and mypy run when installed
# (the hermetic CI image ships neither — their defect classes are covered
# natively by mpclint MPL6xx); mpclint + mpcflow + mpcshape always run
# and are the gate — check_all parses the AST once and feeds all three.
lint:
	@if $(PY) -c "import ruff" 2>/dev/null || command -v ruff >/dev/null 2>&1; then \
	  echo "== ruff"; ruff check mpcium_tpu/ scripts/ tests/ || exit $$?; \
	else echo "== ruff not installed — skipped (MPL6xx covers its classes)"; fi
	@if $(PY) -c "import mypy" 2>/dev/null; then \
	  echo "== mypy"; $(PY) -m mypy mpcium_tpu/wire.py mpcium_tpu/config.py mpcium_tpu/utils/ || exit $$?; \
	else echo "== mypy not installed — skipped"; fi
	@echo "== mpclint + mpcflow + mpcshape"; $(PY) scripts/check_all.py

# the one-pass static gate alone (mpclint + mpcflow + mpcshape +
# budget/surface drift, shared AST parse) — what CI calls between edit
# and test; the trace gate rides along (--no-sweep: the sweep just ran)
check:
	$(PY) scripts/check_all.py
	$(PY) scripts/trace_check.py --no-sweep

# compile-surface gate alone (STATIC_ANALYSIS.md "Compile surface"):
# MPS9xx rules + COMPILE_SURFACE.json drift. Run
# scripts/mpcshape_surface.py (no --check) after an intentional
# signature change, review the diff, commit the JSON.
shapecheck:
	$(PY) scripts/mpcshape_surface.py --check

# warm-manifest gate alone (PERFORMANCE.md "Warm start"): the pre-warm
# work-list must enumerate exactly surface knobs × engine/buckets with
# no silent gaps — pure stdlib, no jax. Also folded into check_all.
warmcheck:
	$(PY) scripts/prewarm.py --check

# fill the XLA persistent cache for this host's serving set (the same
# pass the daemon runs at boot with warm_enabled; see scripts/prewarm.py
# for scheme/bucket/budget flags)
prewarm:
	$(PY) scripts/prewarm.py

# mpctrace gate alone (OBSERVABILITY.md): committed TRACE_sample.json
# validates + covers every instrumented layer, and a traced protocol
# run is transcript-identical to an untraced one; includes the static
# sweep so it is self-contained. --regen rebuilds the sample.
trace-check:
	$(PY) scripts/trace_check.py

# smoke tier (< ~1 min target on a laptop core; full crypto suites are slow-marked)
test:
	$(PY) -m pytest tests/ -m "not slow" -q

# per-file: XLA's CPU AOT cache deserialization can segfault rarely in
# very long single processes on some hosts; file-scoped runs are isolated
# (and each file's kernels stay warm in the persistent cache)
test-all:
	@set -e; for f in tests/test_*.py; do \
	  echo "== $$f"; \
	  rc=0; $(PY) -m pytest "$$f" -q --no-header || rc=$$?; \
	  if [ $$rc -ge 128 ]; then \
	    echo "== crash (rc=$$rc); retrying without compile cache (AOT flake isolation): $$f"; \
	    MPCIUM_TESTS_NO_CACHE=1 $(PY) -m pytest "$$f" -q --no-header; \
	  elif [ $$rc -ne 0 ]; then \
	    echo "== FAILED (rc=$$rc): $$f"; exit $$rc; \
	  fi; \
	done

bench:
	$(PY) bench.py

# chaos drills (ISSUE 3): the full catalog, JSON reports, non-zero exit
# on any missed expected outcome; reproduce a failure with --seed
chaos:
	$(PY) scripts/chaos_drill.py --seed 7

chaos-tests:
	$(PY) -m pytest tests/ -m chaos -q

# SLO load soak (ISSUE 6): bursty mixed traffic + batch-chaos fault plan,
# accounting invariant enforced (non-zero exit on any silent drop)
soak:
	$(PY) scripts/load_soak.py --out SOAK_local.json

soak-tests:
	$(PY) -m pytest tests/ -m soak -q

# dev stack: durable broker on :4333 (the docker-compose/nats analogue)
broker:
	$(PY) -m mpcium_tpu.cli.main broker --port 4333 --journal ./broker-queue.jsonl

setup-identities:
	bash scripts/setup_identities.sh

setup-initiator:
	bash scripts/setup_initiator.sh

clean:
	rm -rf db control broker-queue.jsonl identity peers.json
