# Developer entry points (the reference's package.json scripts analog).
# Tests and dryruns run on CPU with 8 virtual devices; bench targets the
# real TPU when one is attached.

PY ?= python
CPU_ENV = JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: test fuzz fuzz-differential fuzz-frames fuzz-crash chaos weak-scaling \
	bench bench-smoke bench-streaming bench-fused entry dryrun lint lint-baseline \
	clean obs fleet perf-gate serve-smoke bench-serve paged-smoke bench-longdoc \
	fused-smoke fleet-serve-smoke bench-fleet-serve bench-markheavy \
	ragged-smoke plan-smoke bench-serve-fused mesh-smoke bench-mesh \
	latency-smoke incident-smoke history-smoke

test:
	$(PY) -m pytest tests/ -x -q

fuzz:
	$(CPU_ENV) $(PY) -m peritext_tpu.testing.fuzz

# device path vs scalar oracle (spans + cursors)
fuzz-differential:
	$(CPU_ENV) $(PY) -m peritext_tpu.testing.fuzz --differential

# crash-consistency: checkpoint mid-stream, kill, restore, repair
fuzz-crash:
	$(CPU_ENV) $(PY) -m peritext_tpu.testing.fuzz --crash-restore

# composed-fault chaos soak: delivery + corruption + peer stalls + injected
# device-round failures + crash-restore vs the byte-equality oracle
chaos:
	$(CPU_ENV) $(PY) scripts/chaos_soak.py --seeds 20

# 1/2/4/8-device virtual-mesh scaling + digest-invariance evidence
weak-scaling:
	$(PY) scripts/weak_scaling.py

# observability smoke (mirrors the CI obs-smoke job): 128-doc streaming
# session with tracing on; asserts a non-empty Perfetto dump parses and
# prints the per-stage summary (artifacts land in /tmp/pt-obs)
obs:
	$(CPU_ENV) $(PY) scripts/obs_smoke.py --out /tmp/pt-obs

# fleet convergence smoke (mirrors the CI fleet-smoke job): an in-process
# multi-host partition/heal episode — asymmetric partition, flapping + slow
# links, lag-ordered gossip heal, fleet-wide digest equality — plus the
# seeded divergence injection (artifacts land in /tmp/pt-fleet)
fleet:
	$(CPU_ENV) $(PY) scripts/fleet_smoke.py --out /tmp/pt-fleet

# serving-tier smoke (mirrors the CI serve-smoke job): overload burst ->
# typed shed verdicts + bounded queue, redelivery -> byte equality, and
# the `obs serve` health-check contract (exit 1 overloaded / 0 healthy);
# artifacts land in /tmp/pt-serve
serve-smoke:
	$(CPU_ENV) $(PY) scripts/serve_smoke.py --out /tmp/pt-serve

# time-to-visibility latency-plane smoke (mirrors the CI obs-smoke job's
# latency step): an armed serve session -> sum-consistent stage records +
# /latency.json + peritext_latency_* families, the `obs why` exit
# contract (0 clean / 1 regressed / 2 unreadable), and the <2% arming
# overhead pin (artifacts land in /tmp/pt-latency)
latency-smoke:
	$(CPU_ENV) $(PY) scripts/latency_smoke.py --out /tmp/pt-latency

# fleet incident-plane smoke (mirrors the CI incident-smoke job): the
# host-kill chaos episode must open EXACTLY a host-death incident and
# resolve it post-heal with time-to-detection reported, the per-host
# flight dumps merge into one cross-host timeline, the `obs incidents`
# / `obs status` / `obs flight` exit contracts hold, and feeding the
# plane compiles ZERO XLA programs (artifacts land in /tmp/pt-incident)
incident-smoke:
	$(CPU_ENV) $(PY) scripts/incident_smoke.py --out /tmp/pt-incident

# fleet history-plane smoke (mirrors the CI history-smoke job): an armed
# serve session retains frames, rolls JSONL segments over, and replays
# them byte-identically with ZERO XLA compiles; the serve-overload chaos
# episode scores as an anomaly no later than its incident opens; the
# `obs history` exit contract (0/1/2) holds; and the history-weighted
# `obs plan` replay is deterministic (artifacts land in /tmp/pt-history)
history-smoke:
	$(CPU_ENV) $(PY) scripts/history_smoke.py --out /tmp/pt-history

# sustained open-loop serving ladder: docs/s at the p99 apply-latency SLO
bench-serve:
	$(PY) bench.py --mode serve

# paged-storage smoke (mirrors the CI paged-smoke job): a small streaming
# session through the padded and paged layouts — byte equality
# (spans/patches/digests), peritext_page_* gauges + /devprof.json
# page_pool section (artifacts land in /tmp/pt-paged)
paged-smoke:
	$(CPU_ENV) $(PY) scripts/paged_smoke.py --out /tmp/pt-paged

# ragged-layout smoke (mirrors the CI ragged-smoke job): the Pallas kernel
# in interpret mode + the lax pool walk against the padded oracle, the
# ragged DocBatch/streaming byte equality, padding_efficiency == 1.0, and
# the peritext_ragged_* gauges (artifacts land in /tmp/pt-ragged)
ragged-smoke:
	$(CPU_ENV) $(PY) scripts/ragged_smoke.py --out /tmp/pt-ragged

# long-tail layout comparison row: one essay among a tweet fleet, padded
# and ragged DocBatch measured, byte equality asserted, waste reported
bench-longdoc:
	$(PY) bench.py --mode longdoc

# fused-pipeline smoke (mirrors the CI fused-smoke job): fused vs
# per-round byte equality across both layouts, staging-overlap direction,
# zero steady-state compiles, fused devprof sites (artifacts in
# /tmp/pt-fused)
fused-smoke:
	$(CPU_ENV) $(PY) scripts/fused_smoke.py --out /tmp/pt-fused

# fleet-serve smoke (mirrors the CI fleet-serve-smoke job): a 3-host
# FleetFrontend under round-robin traffic, one serving host killed
# mid-traffic — lease death detection, checkpoint+journal failover,
# typed verdicts only, acked-op survival, post-heal byte equality, and
# the /fleet.json + peritext_fleet_* exporter surface (artifacts land
# in /tmp/pt-fleet-serve)
fleet-serve-smoke:
	$(CPU_ENV) $(PY) scripts/fleet_serve_smoke.py --out /tmp/pt-fleet-serve

# host-kill failover episode as a measurement: fleet frames applied/s
# with every failover oracle asserted in-row
bench-fleet-serve:
	$(PY) bench.py --mode fleet-serve

# device-as-OS planner smoke (mirrors the CI plan-smoke job): 32 tenants
# fuse into one staged dispatch per window (byte equality vs per-session
# drains), then the closed-loop planner proposes statics from the captured
# devprof snapshot and the proposal replays through the bench row
plan-smoke:
	$(CPU_ENV) $(PY) scripts/plan_smoke.py --out /tmp/pt-plan

# multi-tenant fused-dispatch row: N small tenants on one lane vs
# per-session drains (dispatch amortization; byte equality in-row)
bench-serve-fused:
	$(PY) bench.py --mode serve-fused

# mesh-sharded host smoke (mirrors the CI mesh-smoke job): 1/2/4/8-shard
# doc-axis drains byte-equal to single-device across all three layouts,
# one shard_map program per drain batch, zero steady-state compiles, the
# collective reshard byte-preserving, peritext_mesh_* gauges rendered
# (artifacts land in /tmp/pt-mesh)
mesh-smoke:
	$(CPU_ENV) $(PY) scripts/mesh_smoke.py --out /tmp/pt-mesh

# sustained mesh drain throughput: the 1/2/4/8-shard rung sweep with byte
# equality and the one-dispatch contract asserted in-row
bench-mesh:
	$(PY) bench.py --mode mesh

# mark-heavy editorial pass (span-overlap explosion) vs the scalar oracle
bench-markheavy:
	$(PY) bench.py --mode markheavy

# streaming frame ingest vs oracle (spans + incremental patch streams)
fuzz-frames:
	$(CPU_ENV) $(PY) -m peritext_tpu.testing.fuzz --differential-frames

bench:
	$(PY) bench.py

bench-smoke:
	$(PY) bench.py --smoke

bench-streaming:
	$(PY) bench.py --mode streaming

# fused device-resident round pipeline vs per-round dispatch (same
# workload, byte equality asserted in-row on every measured seed)
bench-fused:
	$(PY) bench.py --mode streaming-fused

bench-engine:  # device-only streaming replay: the engine limit vs the link
	$(PY) bench.py --mode engine

# perf-regression gate (mirrors the CI perf-gate job): CPU mini-ladder with
# devprof sampling appended to a scratch copy of the committed reference
# ledger, then gated with per-row tolerance bands (exit 1 on regression)
perf-gate:
	cp perf/reference_ledger.jsonl /tmp/pt-perf-gate.jsonl
	PT_BENCH_LADDER_ROWS="streaming,streaming_fused,wire,serve_sustained,serve_multitenant,batch_longdoc,batch_8k_ragged,markheavy,fleet_serve,serve_mesh_sustained" $(PY) bench.py \
		--mode ladder --smoke --platform cpu --devprof \
		--ledger /tmp/pt-perf-gate.jsonl
	$(PY) -m peritext_tpu.obs perf /tmp/pt-perf-gate.jsonl --gate

entry:
	$(PY) -c "import jax; jax.config.update('jax_platforms','cpu'); \
	import __graft_entry__ as g; fn, a = g.entry(); \
	jax.block_until_ready(jax.jit(fn)(*a)); print('entry OK')"

dryrun:
	$(CPU_ENV) $(PY) -c "import jax; jax.config.update('jax_platforms','cpu'); \
	import __graft_entry__ as g; g.dryrun_multichip(8)"

# Lint = syntax floor (compileall) + graftlint, the project's determinism &
# tracer-safety suite (rules PTL001-PTL006; see DESIGN.md "Determinism
# contract").  Known intentional violations are attributed in
# graftlint_baseline.json; anything new fails here and in CI.
# CI additionally runs ruff with the config in pyproject.toml.
lint:
	$(PY) -m compileall -q peritext_tpu tests demos scripts bench.py __graft_entry__.py
	$(PY) -m peritext_tpu.analysis peritext_tpu

# regenerate the graftlint baseline (justify any new TODO entries by hand)
lint-baseline:
	$(PY) -m peritext_tpu.analysis peritext_tpu --update-baseline --baseline graftlint_baseline.json

clean:
	rm -rf peritext_tpu/native/_build .pytest_cache
	find . -name __pycache__ -type d -exec rm -rf {} +
