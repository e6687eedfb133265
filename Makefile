# Mirrors the reference's Makefile contract (race-enabled suite with a
# wall-clock budget, Makefile:1-6). `test` is the fast tier — the
# control-plane/unit surface, the analog of the reference's 35 s suite;
# `test-all` adds the XLA-compile-heavy ML tests and the multiprocess/
# failover/scale drills (the `slow` marker, tests/conftest.py).

.PHONY: test test-all lint native chip-smoke chaos obs-demo health-demo serve-obs-demo

test:
	python -m pytest tests/ -x -q -m "not slow"

test-all:
	python -m pytest tests/ -q

# Seeded chaos soak (docs/OPERATIONS.md "Chaos drills"): a FRESH random
# fault schedule against the in-process trainer + registry +
# coordinator stack every run. On failure the harness prints the
# FaultPlan JSON; replay the exact schedule with
# PTYPE_CHAOS_SOAK_SEED=<seed> make chaos.
chaos:
	PTYPE_CHAOS_SOAK_SEED=$${PTYPE_CHAOS_SOAK_SEED:-$$(date +%s)} \
		python -m pytest tests/test_chaos_soak.py -q

# Distributed-tracing walkthrough (docs/OBSERVABILITY.md): a traced
# in-process fleet (coordinator + two workers over real sockets +
# gateway) serves a few requests — one under a seeded chaos fault —
# then the cluster telemetry snapshot is pulled over actor RPC and a
# stitched Chrome trace (Perfetto-loadable) is written.
obs-demo:
	JAX_PLATFORMS=cpu python examples/observability/demo.py

# Cluster health plane walkthrough (docs/OBSERVABILITY.md "Health
# plane & alerting"): a simulated 3-worker fleet with per-node goodput
# ledgers + samplers, a seeded chaos straggler fault on one worker's
# store.push — the alert engine names the afflicted node from the
# stitched cluster snapshot and the `obs top` view renders it.
health-demo:
	JAX_PLATFORMS=cpu python examples/observability/health_demo.py

# Serving observability walkthrough (docs/OBSERVABILITY.md "Serving
# plane"): a traced 2-replica paged fleet takes a shared-prefix burst
# through the gateway; the serving ledger's TTFT/TPOT/KV series feed
# the `obs serve` view and one stitched Perfetto export lands in
# $OBS_DIR/serve_trace.json.
serve-obs-demo:
	JAX_PLATFORMS=cpu python examples/observability/serve_demo.py

# The main path on the attached TPU, one process, every chip the host
# shows: optimus-125M through the Trainer (and the Store-DP trainer on
# >1 chip), the paged server behind the gateway over real sockets, and
# every Pallas kernel against its float32 reference — the only tier
# that sees the Mosaic compiler. No TPU is a FAILURE (non-zero exit),
# never a skip; the last stdout line is the JSON verdict.
chip-smoke:
	python chip_smoke.py

# Real static analysis (reference bar: golangci-lint, .golangci.yml):
# the stdlib-only ptlint package (tools/ptlint) — the pyflakes-grade
# base checks plus the PT001–PT017 house rules (catalogue:
# docs/LINTING.md; suppressions are `# ptlint: disable=PTxxx -- why`
# and MUST carry the justification). Also invoked from the tier-1
# suite (tests/test_ptlint.py), so a broken linter fails `make test`
# too.
lint:
	python -m tools.ptlint ptype_tpu tools tests examples chip_smoke.py __graft_entry__.py
	python -m compileall -q ptype_tpu

# Native wire transport (writev frame sends, GIL-free reads, crc32c).
# ptype_tpu.native also builds this lazily on first load.
native:
	g++ -O3 -fPIC -shared -o ptype_tpu/_ptype_wire.so native/ptype_wire.cpp
