# Developer entry points. The tier-1 gate is `make test` (everything);
# `make test-fast` skips interpret-mode Pallas parity tests (marked
# `slow` — they run the kernels through the CPU interpreter and
# dominate suite wall-clock).  `make test-tp` runs the tensor-parallel
# suite under 8 forced host devices (its tests also subprocess their
# own device counts, so it works from any environment).  `make test-dit`
# runs the diffusion (DiT) suite including its slow kernel-path tests.
# `make docs-check` import-checks every python code block in
# README.md/docs/, every examples/ module, and the configs registry
# (each config module must be registered) so docs/configs can't rot.
# `make test-chaos` runs the reliability suite (fault models, degraded
# mode, and the deterministic chaos soak against the hardened engines)
# including its slow-marked soak tests.
# `make test-attn` runs the decode-attention kernel suite (int8-KV,
# split-KV, ring-buffer edge cases — slow-marked interpret-mode tests
# included) plus the TP sharded-KV-cache parity test.
# `make test-serving` runs the serving suite: block-allocator property
# tests, the paged flash-decode bit-identity pins, both continuous-
# batching engines (ring + paged), and the traffic-harness checks.
# `make test-obs` runs the observability suite: metrics/exporters,
# per-request span logs (deterministic, exactly-once close on every
# terminal path), manifest-derived dispatch counts, and the energy
# attribution vs the analytic simulator.
# `make audit` proves the CIM execution contract statically: it traces
# every full-plan arch abstractly (prefill / ring / paged decode,
# split-KV, TP-2 per-shard, DiT) and diffs the pallas dispatch
# schedule, dtype flow, collectives and VMEM footprints against
# src/repro/analysis/manifest.py, then drives the serving retrace
# guard.  `make lint` enforces the ruff.toml hygiene rules (ruff when
# installed, stdlib-AST fallback otherwise).
# `make verify` is the pre-push check: lint + fast tests + docs-check +
# the multi-device TP suite + the attention suite + the serving suite +
# the DiT suite + the chaos/reliability suite + the contract audit,
# plus a BENCH smoke run (simulator + serving
# rows; merges into
# BENCH_kernels.json without clobbering the kernel rows — a full
# `make bench` additionally prunes rows for renamed/deleted benches and
# measures the resilience_ber_* chaos rows).
PY := PYTHONPATH=src python

.PHONY: test test-fast test-tp test-dit test-chaos test-attn test-serving test-obs bench verify docs-check audit lint

test:
	$(PY) -m pytest -x -q

test-fast:
	$(PY) -m pytest -x -q -m "not slow"

test-tp:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PY) -m pytest -x -q tests/test_tp.py

test-dit:
	$(PY) -m pytest -x -q tests/test_diffusion.py

test-chaos:
	$(PY) -m pytest -x -q tests/test_reliability.py

test-attn:
	$(PY) -m pytest -x -q tests/test_kernels.py -k "DecodeAttention"
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PY) -m pytest -x -q tests/test_tp.py -k "kv_cache_sharded"

test-serving:
	$(PY) -m pytest -x -q tests/test_serving.py

test-obs:
	$(PY) -m pytest -x -q tests/test_obs.py

docs-check:
	$(PY) tools/check_docs.py

audit:
	XLA_FLAGS=--xla_force_host_platform_device_count=2 \
	$(PY) tools/audit_jaxpr.py

lint:
	$(PY) tools/lint.py

bench:
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	$(PY) -m benchmarks.run

verify: lint test-fast docs-check test-tp test-attn test-serving test-obs test-dit test-chaos audit
	$(PY) -m benchmarks.run --skip-kernels
