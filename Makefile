# kubetorch-tpu dev entry points.
#
# Tests and dev drives run on the CPU; the chip is reached only through
# `python chip_smoke.py` on a machine that has one (see
# .claude/skills/verify/SKILL.md).

PY_CPU := JAX_PLATFORMS=cpu
PY_MESH := $(PY_CPU) XLA_FLAGS="--xla_force_host_platform_device_count=8"

.PHONY: test test-fast soak soak-smoke test-chaos test-store-chaos test-ring test-elastic test-sched test-serve test-federation test-shm test-rollout test-pipeline test-flywheel lint perf-gate bench bench-store bench-trace bench-ckpt bench-fleet bench-serve bench-scale-out bench-federation bench-hotpath bench-rollout bench-step bench-pipeline bench-flywheel bench-obs dryrun native clean

# full matrix (everything but the real-chip tier) — the release gate.
# perf-gate rides along (ISSUE 10, grown in 11/12): the full stage budget
# (deserialize/queue_wait/execute/store_fetch/shm_copy/rollout_apply/
# train_step/snapshot_stall) is enforced on every release-gate run, not
# just when someone remembers to ask.
test:
	$(PY_CPU) python -m pytest tests/ -q
	$(PY_CPU) python scripts/check_perf_gate.py --retries 3
	$(MAKE) soak-smoke

# fast default tier (<3 min): skips the jit-heavy pipeline/parallel/model
# release matrix; run before every commit
test-fast:
	$(PY_CPU) python -m pytest tests/ -q -x --level minimal

# fault-injection suite (ISSUE 2): deterministic KT_CHAOS schedules with a
# fixed seed — kept out of the tier-1 default path (see docs/resilience.md)
test-chaos:
	$(PY_CPU) KT_CHAOS_SEED=1234 python -m pytest tests/ -q -m chaos

# store crash/corruption suite (ISSUE 4): torn-write SIGKILL mid-PUT,
# corrupt-blob → scrub quarantine, disk-full → typed 507, startup recovery
test-store-chaos:
	$(PY_CPU) KT_CHAOS_SEED=1234 python -m pytest tests/test_store_chaos.py -q

# replicated-ring suite (ISSUE 7): placement stability, replica
# forwarding at W=2, proxy reads, epoch mismatch, TTL re-replication,
# and the SIGKILL-mid-push/pull chaos acceptance (subprocess fleets)
test-ring:
	$(PY_CPU) KT_CHAOS_SEED=1234 python -m pytest tests/test_store_ring.py -q

# elastic SPMD suite (ISSUE 6): kill-rank → N-1 re-mesh resume from the
# last committed checkpoint; term-rank → drain-and-checkpoint in the grace
# window; commit-marker torn-upload safety; split restart budgets
test-elastic:
	$(PY_CPU) KT_CHAOS_SEED=1234 python -m pytest tests/ -q -m elastic

# scheduler suite (ISSUE 8): priority tiers, capacity book, preemption via
# the drain path, checkpoint-commit inside the grace window, transparent
# resume with zero lost committed steps, scheduler-state durability
test-sched:
	$(PY_CPU) KT_CHAOS_SEED=1234 python -m pytest tests/ -q -m sched

# serving front-door suite (ISSUE 9): router packing/affinity/admission,
# shed-before-prefill (no execute span for shed requests), health TTL
# cache, session glue, queue-wait autoscale parsing
test-serve:
	$(PY_CPU) KT_CHAOS_SEED=1234 python -m pytest tests/ -q -m serve

# planet-scale federation suite (ISSUE 13): region taxonomy, lease/epoch
# fencing, cross-region anti-entropy + checkpoint fallback, geo spill
# with typed shedding, the kill-region/partition verbs, and the
# whole-region-death acceptance drill (slow+chaos)
test-federation:
	$(PY_CPU) KT_CHAOS_SEED=1234 python -m pytest tests/test_federation.py -q

# elastic pipeline suite (ISSUE 17): membership/re-group/epoch-fence units,
# stage-gang admission + partial preemption, the generic-schedule
# bit-identity pins, and the real-subprocess stage-SIGKILL/stall drills
test-pipeline:
	$(PY_CPU) KT_CHAOS_SEED=1234 python -m pytest tests/ -q -m pipeline --level release

# continuous-learning flywheel suite (ISSUE 19): feedback-ledger durability
# (quorum-acked segments, at-least-once cursor with hash dedup, epoch-fenced
# leases), harvest/vacate policy + grace-window exits, gated promotion
# (eval gate -> canary -> promote/rollback), kill-flywheel/drop-ack chaos
# verbs, and the loss-proof soak invariant
test-flywheel:
	$(PY_CPU) KT_CHAOS_SEED=1234 python -m pytest tests/ -q -m flywheel

# resilience lint: no raw requests.* call sites may bypass the retry layer
lint:
	$(PY_CPU) python scripts/check_resilience.py

# seeded chaos-conductor soak (ISSUE 15). soak-smoke is the CI tier: a
# fixed-seed ~60s store+train schedule whose invariant verdict gates
# `make test`; `make soak` is the long operator run over every profile.
# Every run arms the flight recorder (ISSUE 20): the seed-20 store line
# is the black-box drill — kill-store-node SIGKILLs under an armed
# spool, and check_blackbox hash-verifies every dead child's spool in
# the post-teardown census (the rank-SIGKILL recovery drill is the
# subprocess test in tests/test_obs.py).
soak-smoke:
	$(PY_CPU) KT_SOAK_OP_INTERVAL_S=0.1 python -m kubetorch_tpu.cli soak run --seed 42 --duration 6 --profile train
	$(PY_CPU) KT_SOAK_OP_INTERVAL_S=0.1 python -m kubetorch_tpu.cli soak run --seed 42 --duration 3 --profile store
	$(PY_CPU) KT_SOAK_OP_INTERVAL_S=0.1 python -m kubetorch_tpu.cli soak run --seed 42 --duration 8 --profile pipeline
	$(PY_CPU) KT_SOAK_OP_INTERVAL_S=0.1 python -m kubetorch_tpu.cli soak run --seed 43 --duration 8 --profile pipeline
	$(PY_CPU) KT_SOAK_OP_INTERVAL_S=0.1 python -m kubetorch_tpu.cli soak run --seed 19 --duration 8 --profile flywheel
	$(PY_CPU) KT_SOAK_OP_INTERVAL_S=0.1 python -m kubetorch_tpu.cli soak run --seed 20 --duration 5 --profile store

soak:
	$(PY_CPU) python -m kubetorch_tpu.cli soak run --seed 42 --duration 60 --profile all
	$(PY_CPU) python -m kubetorch_tpu.cli soak run --seed 43 --duration 60 --profile federation
	$(PY_CPU) python -m kubetorch_tpu.cli soak run --seed 44 --duration 60 --profile store

# per-stage perf regression gate (ISSUE 9, expanded in 10–12): dispatch,
# store, shm, rollout, train_step, and snapshot_stall p50 through the
# real pod-server + store + shm-envelope + jitted-step paths vs the
# committed baseline (scripts/perf_baseline.json); >10%+floor fails
perf-gate:
	$(PY_CPU) python scripts/check_perf_gate.py

# zero-copy envelope suite (ISSUE 10): ring protocol units, e2e pool
# round trips, chaos shm-corrupt -> typed fallback, /dev/shm lifecycle
test-shm:
	$(PY_CPU) KT_CHAOS_SEED=1234 python -m pytest tests/test_shm_ring.py -q

# live weight rollout suite (ISSUE 11): broadcast-tree protocol units,
# delta apply/fingerprint gate/rollback, canary pinning + auto-rollback,
# kill-peer chaos parse/scoping, mid-broadcast SIGKILL acceptance
test-rollout:
	$(PY_CPU) KT_CHAOS_SEED=1234 python -m pytest tests/test_rollout.py -q

bench:
	python bench.py

# data-plane microbench: pytree put/get MB/s, cold vs delta (ISSUE 1)
bench-store:
	$(PY_CPU) python scripts/bench_datastore.py

# telemetry overhead budget (ISSUE 5): put/get hot path, tracing off vs on
# — enforced <3% enabled, ~0% disabled (the allocation-free fast path)
bench-trace:
	$(PY_CPU) python scripts/bench_datastore.py --trace-overhead

# store-fleet regime (ISSUE 7): cold + delta sync MB/s vs ring size
# (1/2/3 nodes, R=2 W=2) — weight distribution as the fleet grows
bench-fleet:
	$(PY_CPU) python scripts/bench_datastore.py --fleet 3

# checkpoint regime (ISSUE 6): per-step committed-checkpoint cost vs the
# fraction of leaves that changed — the "~free suspend/resume" claim,
# BENCH-tracked
bench-ckpt:
	$(PY_CPU) python scripts/bench_datastore.py --checkpoint

# serving front-door bench (ISSUE 9): 1200 open-loop sessions through the
# REAL router — TTFT p50/p99, tokens/s, shed rate, affinity hit rate,
# rr-vs-affinity on the same seeded arrival schedule
bench-serve:
	$(PY_CPU) python scripts/bench_serve.py

# fleet cold-start burn-down (ISSUE 16): 0->N replicas cold (fresh
# interpreter, empty AOT cache) vs warm (pre-warmed template fork + shm
# weight attach + persistent AOT executable cache) — p50/p99
# time-to-first-token-served with per-phase anatomy — plus 0->16 joiners
# pulling weights over the /route broadcast tree (~1x origin egress)
bench-scale-out:
	$(PY_CPU) python scripts/bench_serve.py --scale-out

# cross-region failover bench (ISSUE 13): subprocess CPU-proxy regions
# behind the geo front door, the primary SIGKILLed mid-run — failover
# time + spillover TTFT p50/p99 + typed-shed accounting (raw errors
# reaching the client must be zero)
bench-federation:
	$(PY_CPU) python scripts/bench_serve.py --regions 2

# dispatch hot-path bench (ISSUE 10): shm envelopes vs the mp-queue path
# through the REAL process pool — p50/p99 per stage-size, MB/s, and the
# msgpack-vs-shm crossover + 2x points, BENCH-tracked
bench-hotpath:
	$(PY_CPU) python scripts/bench_hotpath.py

# live-rollout bench (ISSUE 11): fleet-wide rollout latency + origin
# egress vs replica count (3/6/12 subprocess replicas) and delta size,
# broadcast tree vs star baseline, with an open-loop load proving zero
# dropped requests across the swap
bench-rollout:
	$(PY_CPU) python scripts/bench_rollout.py

# step-anatomy A/B (ISSUE 12): overlapped grad reduction vs plain accum
# on the forced 8-device host mesh (bit-comparability, accumulator shard
# fraction, compiled temp bytes) + the blocking-vs-async snapshot stall
# for a >=64MB state (>=10x required) — bench-convention JSON
bench-step:
	python bench.py --step-overlap

# fleet-aggregator demo (ISSUE 20): multi-replica pod /metrics scrapes
# merged into the kt_fleet_* rollup — merged p50/p99 must match a
# single-scrape reference within tolerance, and an injected delay breach
# must trip the fast-window SLO burn alert within one scrape interval —
# exit-coded acceptance
bench-obs:
	$(PY_CPU) python scripts/bench_serve.py --obs

# flywheel closed-loop bench (ISSUE 19): open-loop serving traffic feeding
# the REAL ledger -> harvester -> promoter stack on a subprocess store —
# feedback-to-weights-live p50/p99, serving TTFT/shed impact vs a no-
# flywheel baseline arm, and vacate-inside-grace exit-coded acceptance
bench-flywheel:
	$(PY_CPU) python scripts/bench_serve.py --flywheel

# elastic-pipeline regime (ISSUE 17): pipelined-vs-SPMD tokens/s at equal
# chips + analytic/measured bubble fraction on the forced 8-device host
# mesh, then a real stage-SIGKILL drill measuring the re-group stall
# (fault detected -> first post-re-group committed step) — bench JSON
bench-pipeline:
	python bench.py --pipeline

dryrun:
	$(PY_MESH) python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

native:
	$(MAKE) -C kubetorch_tpu/native

clean:
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null; true
