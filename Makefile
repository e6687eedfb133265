# Mirrors the reference's Makefile contract (race-enabled suite with a
# wall-clock budget, Makefile:1-6). `test` is the fast tier — the
# control-plane/unit surface, the analog of the reference's 35 s suite;
# `test-all` adds the XLA-compile-heavy ML tests and the multiprocess/
# failover/scale drills (the `slow` marker, tests/conftest.py).

.PHONY: test test-all bench serve-bench spec-bench disagg-bench scale-bench traffic-bench collectives-bench hier-bench zero-bench profile-bench jitwatch-bench lint native chip-smoke chaos obs-demo health-demo serve-obs-demo

test:
	python -m pytest tests/ -x -q -m "not slow"

test-all:
	python -m pytest tests/ -q

bench:
	python bench.py

# Serving tail-latency microbench through the inference gateway
# (docs/OPERATIONS.md "Serving at scale"): three replicas, one slow;
# the JSON tail carries serve_p99_ms / serve_tokens_per_sec via the
# gateway and the round-robin comparison p99, plus the paged-engine
# probe's serve_prefix_hit_speedup / serve_kv_util_pct /
# serve_prefill_stall_ms (shared-prefix workload, affinity-routed,
# chunked admission — the ISSUE 9 acceptance numbers).
serve-bench:
	JAX_PLATFORMS=cpu python bench.py --serve

# Speculative-decoding microbench (docs/PERF.md "Speculative
# decoding"): batch-1 single-stream decode tokens/sec through the
# paged engine with draft-propose + batched target-verify vs the
# plain engine, at bit-identical greedy output, plus the measured
# accept rate — the ISSUE 12 acceptance numbers. Also emitted in the
# serve-bench tail.
spec-bench:
	JAX_PLATFORMS=cpu python bench.py --spec

# Disaggregated-serving microbench (docs/OPERATIONS.md
# "Disaggregated serving"): the same mixed long-prompt/short-decode
# load through an interleaved fleet vs a prefill+decode split with
# KV-block migration — the JSON tail carries disagg_ttft_p99_ms vs
# interleaved_ttft_p99_ms (prefill isolation must win),
# migrate_ms_per_block (q8 wire) and migrate_dedup_ratio (chain-hash
# manifest on a shared-prefix family) — the ISSUE 16 acceptance
# numbers.
disagg-bench:
	JAX_PLATFORMS=cpu python bench.py --disagg

# Elastic-reconciler microbench (docs/OPERATIONS.md "Elastic
# serving"): a reconciler-managed fleet behind the gateway — the JSON
# tail carries scale_up_latency_s (first shed -> new replica
# answering, the spike-to-capacity lag) and drain_lost_requests
# (graceful drain under continuous traffic; the bar is 0) — the
# ISSUE 13 acceptance numbers.
scale-bench:
	JAX_PLATFORMS=cpu python bench.py --scale

# Open-loop traffic observatory (docs/OBSERVABILITY.md "Traffic
# plane", docs/OPERATIONS.md "Capacity planning"): one seeded trace
# replayed open-loop at >= 5 offered rates through the gateway +
# reconciler fleet — the JSON tail carries the capacity frontier with
# its located knee (traffic_knee_rps / traffic_goodput_at_knee_pct /
# traffic_ttft_p99_ms_open_loop), the diurnal-spike drill (the
# reconciler-armed fleet must hold the TTFT p99 SLO through the
# replayed spike the static fleet fails), scale-up-latency vs burst
# steepness, and the shed-rate-vs-burn-budget curve — the ISSUE 19
# acceptance numbers. Replay any run with PTYPE_TRAFFIC_SEED=<seed>.
traffic-bench:
	JAX_PLATFORMS=cpu python bench.py --traffic

# Gradient-wire microbench on the 8-device virtual host mesh
# (docs/PERF.md "Quantized + overlapped collectives"): bucketed
# allreduce GB/s per wire format (fp32 / per-chunk int8 / block-scaled
# int8 sweep), quantized push_tree timing, and the goodput ledger's
# collective share of store-DP step time with fine-grained overlap
# off vs on (the ISSUE 6 acceptance numbers).
collectives-bench:
	JAX_PLATFORMS=cpu XLA_FLAGS="$(XLA_FLAGS) --xla_force_host_platform_device_count=8" \
		python bench.py --collectives

# Hierarchical-collectives microbench on the 8-device emulated
# asymmetric host mesh (docs/PERF.md "Hierarchical collectives"):
# hierarchical vs flat bucketed-allreduce step time at exact-wire
# parity for every (outer, inner) factorization of 8, the measured
# slow-leg wire bytes (acceptance: <= 1/n_inner of the flat outer
# footprint), and the per-leg bandwidth model's pricing of the
# emulated ICI/DCN asymmetry (the ISSUE 18 numbers).
hier-bench:
	JAX_PLATFORMS=cpu XLA_FLAGS="$(XLA_FLAGS) --xla_force_host_platform_device_count=8" \
		python bench.py --hier

# ZeRO-1 sharded-optimizer microbench on the 8-device virtual host
# mesh (docs/PERF.md "Sharded optimizer update (ZeRO-1)"): per-replica
# optimizer-state bytes and step time for zero=True vs the replicated
# store-DP baseline (exact + int8/EF wires), plus the goodput ledger's
# optimizer_ms leg — the ISSUE 7 acceptance numbers.
zero-bench:
	JAX_PLATFORMS=cpu XLA_FLAGS="$(XLA_FLAGS) --xla_force_host_platform_device_count=8" \
		python bench.py --zero

# Profiling-plane microbench on the 8-device virtual host mesh
# (docs/OBSERVABILITY.md "Profiling plane"): the capture-disabled
# overhead of the armed plane on the store-DP loop (<1% acceptance),
# the live-capture step cost, and the compiled-vs-analytic FLOPs gap
# on the 125M config (XLA cost_analysis, layer scan unrolled) — the
# ISSUE 8 acceptance numbers.
profile-bench:
	JAX_PLATFORMS=cpu XLA_FLAGS="$(XLA_FLAGS) --xla_force_host_platform_device_count=8" \
		python bench.py --profile

# Recompile-watchdog microbench (docs/LINTING.md "The runtime half"):
# the armed jitwatch hot-region price — transfer-guard entry per
# dispatch, charged against an engine-shaped step with its one host
# sync per iteration (<5% acceptance bar), plus a
# zero-steady-state-recompiles check on the probe itself — the
# ISSUE 15 acceptance numbers. Also emitted in the headline bench
# tail as jitwatch_overhead_pct.
jitwatch-bench:
	JAX_PLATFORMS=cpu python bench.py --jitwatch

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
# suite with a <10 s wall budget (tests/test_ptlint.py), so a broken
# or slow linter fails `make test` too.
lint:
	python -m tools.ptlint ptype_tpu tools tests examples bench.py chip_smoke.py __graft_entry__.py
	python -m compileall -q ptype_tpu

# Native wire transport (writev frame sends, GIL-free reads, crc32c).
# ptype_tpu.native also builds this lazily on first load.
native:
	g++ -O3 -fPIC -shared -o ptype_tpu/_ptype_wire.so native/ptype_wire.cpp
