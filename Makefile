PYTHON ?= python

# Tier-1 verification (ROADMAP): the full suite, fail-fast.
.PHONY: test
test:
	./scripts/test.sh full

# Planner + core tests only — skips the slow kernel sweeps and end-to-end
# system/arch tests.  This is what CI runs on every push.  The file list
# lives in scripts/test.sh (single source of truth).
.PHONY: test-fast
test-fast:
	./scripts/test.sh fast

# Critical-tier lint (see ruff.toml): syntax errors, undefined names.
.PHONY: lint
lint:
	$(PYTHON) -m ruff check src tests benchmarks examples

# Metering smoke: search two tiny stores in-process under different
# objectives and diff them (the power/performance trade-off table).
.PHONY: report
report:
	PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH)) $(PYTHON) -m repro.metering.report --selftest

# Serving load smoke: Poisson arrival trace through the ServeEngine on the
# reduced config — tok/s, p50/p99 latency and joules/token with provenance.
# The machine-readable snapshot lands in BENCH_serve.json for run-over-run
# diffs.
.PHONY: serve-bench
serve-bench:
	PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH)) $(PYTHON) benchmarks/serve_load.py --fast --meter auto --json-out BENCH_serve.json

# Same trace on the block-paged KV cache (chunked prefill on): pool
# utilization / stranded / fragmentation stats alongside the tok/s numbers.
.PHONY: serve-bench-paged
serve-bench-paged:
	PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH)) $(PYTHON) benchmarks/serve_load.py --fast --meter auto --page-size 16 --prefill-chunk 8 --json-out BENCH_serve_paged.json

# Paged-attention microbench: fused page walk vs the XLA block walk across
# page sizes — measured latency where the kernel can run, static
# peak-live-bytes everywhere.  Snapshot lands in BENCH_paged_attn.json.
.PHONY: bench-paged-attn
bench-paged-attn:
	PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH)) $(PYTHON) benchmarks/paged_attention_bench.py --json-out BENCH_paged_attn.json

# Observability demo: run the fast serving trace with the lifecycle
# tracer on, write trace-demo.json (loadable at ui.perfetto.dev) and a
# Prometheus snapshot, then print the terminal span summary.
.PHONY: trace-demo
trace-demo:
	PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH)) $(PYTHON) benchmarks/serve_load.py --fast --trace-out trace-demo.json --metrics-out trace-demo-metrics.txt
	PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH)) $(PYTHON) -m repro.obs.timeline trace-demo.json --check

# Static analysis: legality + resource-envelope + hot-path + paging
# passes over every zoo (arch, phase) program and two tiny serve engines,
# ratcheted against the checked-in analysis_baseline.json — CI fails only
# on NEW findings.  Resource verdicts check the static cpu-host-16g
# envelope so they are identical on every host; then a serve preflight
# proves the static capacity gate passes for a config that fits.
.PHONY: analyze
analyze:
	PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH)) $(PYTHON) -m repro.analysis.lint --resources --fail-on-new
	PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH)) $(PYTHON) -m repro.launch.serve --arch llama3.2-1b --reduced --slots 2 --max-len 64 --page-size 16 --envelope cpu-host-16g --preflight

# Static capacity check of a serve deployment without booting the engine
# (override ARCH/ENVELOPE/PREFLIGHT_ARGS as needed).
.PHONY: preflight
ARCH ?= llama3.2-1b
ENVELOPE ?= host
PREFLIGHT_ARGS ?= --reduced --slots 2 --max-len 64
preflight:
	PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH)) $(PYTHON) -m repro.launch.serve --arch $(ARCH) --envelope $(ENVELOPE) $(PREFLIGHT_ARGS) --preflight

.PHONY: deps-dev
deps-dev:
	$(PYTHON) -m pip install -r requirements-dev.txt
