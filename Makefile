# Developer entry points (the reference drives its native build + tests from
# make, `/root/reference/Makefile`; here the native loader builds itself on
# first import, so these are conveniences).

PY ?= python
SHELL := /bin/bash  # verify uses pipefail/PIPESTATUS

.PHONY: test test-fast verify lint native bench chip-smoke dryrun chaos chaos-kill \
	chaos-preempt preempt-smoke chaos-multiproc multiproc-smoke \
	chaos-stream stream-smoke serve-bench \
	serve-smoke vocab-bench vocab-smoke obs-bench obs-smoke fresh-bench \
	fresh-smoke fleet-bench fleet-smoke trace-bench trace-smoke \
	control-bench control-smoke overlap-bench overlap-smoke \
	exchange-occupancy exchange-smoke clean

test:
	$(PY) -m pytest tests/ -q

# repo-invariant linter: AST rules (GL1xx, incl. GL124 stale
# suppressions), the concurrency pass (threadlint GL120-GL123 lock
# discipline + GL125 thread-root registry — library package only,
# sharing the one pyproject/repo context parse, so verify cost stays
# flat) + trace-time jaxpr audit of the step builders against committed
# fingerprints (tests/data/).
# Regenerate fingerprints after an INTENTIONAL structural change with
#   $(PY) tools/graftlint.py --update-fingerprints
lint:
	$(PY) tools/graftlint.py

# serving engine load test: step throughput (int8 serve vs f32 eval)
# plus p50/p99/p99.9 latency vs offered QPS through the micro-batcher,
# across {f32,int8} x {all-device,tiered} x batcher deadlines
# (tools/profile_serve.py; budgets recorded in docs/BENCHMARKS.md r8)
serve-bench:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH $(PY) tools/profile_serve.py

# the make-verify tier of the serve bench: tiny world, a few hundred
# requests; asserts finite latency percentiles and exact load-shed
# rejection accounting (timeout-guarded like the pytest tier — a wedged
# compile or thread must fail the gate, not hang it)
serve-smoke:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH timeout -k 10 300 \
	  $(PY) tools/profile_serve.py --smoke

# dynamic-vocabulary churn bench: power-law ids with a drifting tail,
# admission (count-min threshold) vs admit-everything on one stream —
# acceptance: admission <= 50% of the row allocations at equal final
# eval loss (tools/profile_dynvocab.py; budget in docs/BENCHMARKS.md r9)
vocab-bench:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH $(PY) tools/profile_dynvocab.py

# the make-verify tier of the vocab bench: tiny stream, same assertions,
# timeout-guarded like the other smoke tiers
vocab-smoke:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH timeout -k 10 300 \
	  $(PY) tools/profile_dynvocab.py --smoke

# telemetry overhead bench: spans/counters on the tiered + dynvocab
# power-law workloads must cost <= 3% of step time with tracing ENABLED,
# the emitted trace.json must SHOW the prefetch-ahead classify
# overlapping the device window on separate tracks, and the registry
# must round-trip through its manifest section
# (tools/profile_telemetry.py; budget in docs/BENCHMARKS.md r10)
obs-bench:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH $(PY) tools/profile_telemetry.py

# the make-verify tier: same structural assertions (trace produced with
# the overlap visible, counters round-trip), overhead only required
# finite — tiny world, timeout-guarded
obs-smoke:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH timeout -k 10 300 \
	  $(PY) tools/profile_telemetry.py --smoke

# streaming chaos: SIGKILL the trainer mid-publish (torn delta tmp), the
# compactor mid-fold, and the subscriber mid-promote; relaunch each and
# assert the folded serve state is bit-exact vs an unkilled reference at
# the same watermark, the chain fingerprints sha256-continuous across
# the trainer kill (publisher ATTACH, no re-root), and cold start from
# the compacted base+tail converges (tools/chaos_stream.py; the long
# variant is @pytest.mark.slow in tests/test_streaming.py)
chaos-stream:
	$(PY) tools/chaos_stream.py

# the make-verify tier of the streaming chaos: 2 worker subprocesses
# (the mid-publish SIGKILL + attach relaunch), subscriber fold and
# compaction checked in-driver — same bit-exactness assertions,
# timeout-guarded like the other smoke tiers
stream-smoke:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH timeout -k 10 480 \
	  $(PY) tools/chaos_stream.py --smoke

# online-learning freshness bench: trainer publishes row-granular deltas
# while a live subscriber+batcher serve concurrent traffic — measures
# train-step->servable lag (stream/freshness_s), delta bytes vs the
# full export, chain convergence, and delta-vs-reexport bit-exactness
# (tools/profile_freshness.py; budget in docs/BENCHMARKS.md r11)
fresh-bench:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH $(PY) tools/profile_freshness.py

# the make-verify tier of the freshness bench: tiny world, same
# structural assertions, timeout-guarded like the other smoke tiers
fresh-smoke:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH timeout -k 10 300 \
	  $(PY) tools/profile_freshness.py --smoke

# open-loop fleet load generator: exactness vs the single-process
# engine (f32 bit-exact incl. tiered; int8/fp8 byte-exact), p50/p99/
# p99.9 vs offered QPS across fleet sizes {1,2,4 owners} with
# per-process telemetry rolled up through the registry merge, and a
# kill-one-replicated-owner-mid-load run proving zero wrong answers
# with counted failover (tools/profile_fleet.py; budgets in
# docs/BENCHMARKS.md r17)
fleet-bench:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH $(PY) tools/profile_fleet.py

# the make-verify tier of the fleet bench: tiny world, 1-2 owners, a
# few hundred requests — same exactness/failover/roll-up assertions,
# timeout-guarded like the other smoke tiers
fleet-smoke:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH timeout -k 10 300 \
	  $(PY) tools/profile_fleet.py --smoke

# distributed-tracing budget: tracing-enabled fleet serve overhead
# <= 3% vs disabled (the PR 10 budget on the fleet path), ONE merged
# Chrome trace from a world-2 multi-process fleet run (router + 2 owner
# processes + device track; clock-offset handshake, rpc-contains-gather
# nesting after correction), and a chaos-injected failover producing a
# flight-recorder bundle whose slowest request's critical path names
# the rpc stage (tools/profile_trace.py; budgets in docs/BENCHMARKS.md
# r18). DE_TPU_KEEP_TRACE=<dir> keeps the merged trace.json.
trace-bench:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH $(PY) tools/profile_trace.py

# the make-verify tier of the trace bench: tiny world, same structural
# assertions (merged tracks, nesting, flight bundle), overhead only
# required finite — timeout-guarded like the other smoke tiers (the
# longer budget covers the two real owner-process spawns, like
# stream-smoke's worker subprocesses)
trace-smoke:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH timeout -k 10 480 \
	  $(PY) tools/profile_trace.py --smoke

# control-plane budget: hedging off/on p99.9 on a slow-replica fleet
# (zero wrong answers, measurable tightening) and a 3x-QPS-step ramp
# where the autoscaler re-sizes the fleet through apply_fleet mid-load
# with zero wrong/zero dropped requests, every decision in the
# replayable control/decisions stream (tools/profile_control.py;
# budgets in docs/BENCHMARKS.md round 20)
control-bench:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH $(PY) tools/profile_control.py

# the make-verify tier of the control bench: tiny world, same
# assertions, timeout-guarded like the other smoke tiers
control-smoke:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH timeout -k 10 300 \
	  $(PY) tools/profile_control.py --smoke

# host-device overlap budget: the same tiered power-law workload run
# serial (overlap_host=False) vs overlapped (batch k+1's classify/gather
# on the HostWorker while step k runs on device) — acceptance: >= 25%
# step-wall reduction with >= 70% of the host pipeline hidden, the
# overlapped wall within 1.15x of max(host, device), the two loss
# streams BIT-IDENTICAL, and the trace showing worker spans strictly
# inside device windows (tools/profile_overlap.py; budgets in
# docs/BENCHMARKS.md round 22)
overlap-bench:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH $(PY) tools/profile_overlap.py

# the make-verify tier of the overlap bench: tiny world, parity + the
# worker-span structural assertion only (CPU step times at toy scale are
# noise), timeout-guarded like the other smoke tiers
overlap-smoke:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH timeout -k 10 300 \
	  $(PY) tools/profile_overlap.py --smoke

# the round-20 fused-exchange pricing: per-round wall, gather-hidden
# fraction (schedule accounting), wire bytes, fused vs pipelined step
exchange-occupancy:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH $(PY) tools/profile_exchange.py \
	  --overlap-occupancy

# the make-verify tier: tiny workload, machinery + loss parity + the
# schedule accounting only (CPU step times at toy scale are noise),
# timeout-guarded like the other smoke tiers
exchange-smoke:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH timeout -k 10 300 \
	  $(PY) tools/profile_exchange.py --overlap-occupancy --smoke

# the tier-1 gate, exactly as ROADMAP.md specifies it (CPU mesh, no slow
# tests, collection errors surfaced but not fatal to the log); lint runs
# first so invariant violations fail fast, then the smoke tiers
verify: lint serve-smoke vocab-smoke obs-smoke fresh-smoke stream-smoke \
	fleet-smoke trace-smoke preempt-smoke multiproc-smoke control-smoke \
	overlap-smoke exchange-smoke
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 870 env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q \
	  -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
	  -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; \
	rc=$${PIPESTATUS[0]}; \
	echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); \
	exit $$rc

test-fast:
	$(PY) -m pytest tests/ -q -x -k "not training and not checkpoint"

# build a pip wheel (includes the C++ loader sources + any prebuilt .so;
# reference parity: setup.py / build_pip_pkg.sh)
wheel:
	$(PY) -m pip wheel --no-deps --no-build-isolation -w dist .

# force-(re)build the native C++ data loader
native:
	$(PY) -c "from distributed_embeddings_tpu.cc import build; print('built:', build(force=True))"

# the driver-facing benchmark (TPU only, refuses any other backend;
# BENCH_AMP=1 for bf16 compute)
bench:
	$(PY) bench.py

# the standing on-chip check: kernels vs XLA, then the DLRM sparse trainer
# at Criteo width on one chip (and on four when present). One process per
# chip at a time: run nothing else on the chip meanwhile. No CPU mode.
chip-smoke:
	$(PY) chip_smoke.py

# resilience chaos run on the virtual CPU mesh: injected NaN batches, a
# transient checkpoint-write fault, and a kill mid-save — must skip,
# retry, auto-resume, converge, and match the uninterrupted trajectory
# bit-for-bit (tools/chaos_train.py; longer variant is the
# @pytest.mark.slow test in tests/test_resilience.py)
chaos:
	$(PY) tools/chaos_train.py

# cross-run SIGKILL chaos: a REAL worker subprocess is SIGKILLed
# mid-save / between steps and relaunched — at the same world and
# RESIZED (elastic restore) — and the stitched trajectory must match an
# unkilled reference with consumed == steps + skipped across lifetimes
# (tools/chaos_kill.py; the multi-cycle variant is @pytest.mark.slow in
# tests/test_elastic.py)
chaos-kill:
	$(PY) tools/chaos_kill.py

# in-run preemption chaos: a REAL pod-member subprocess is SIGKILLed
# while the pod trains — the surviving trainer quiesces and resizes IN
# PLACE (resilience.elastic.elastic_resize, no checkpoint restore
# round-trip: the ckpt root stays empty), then regrows when a
# replacement member registers; a SIGTERM'd worker drains gracefully
# (finish the in-flight step, snapshot, exit 0 within its deadline) and
# resumes bit-exact. Trajectory checked against an unkilled same-data
# reference; consumed == steps + skipped across the whole run
# (tools/chaos_preempt.py; the full run adds a shrink-to-world-1 cycle)
chaos-preempt:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH $(PY) tools/chaos_preempt.py

# the make-verify tier of the preemption chaos: fewer steps, same
# assertions (SIGKILL shrink + regrow with no restore round-trip,
# SIGTERM drain + bit-exact resume), timeout-guarded like the other
# smoke tiers (the budget covers the reference + pod + drain relaunch
# worker processes, each of which compiles its own steps)
preempt-smoke:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH timeout -k 10 540 \
	  $(PY) tools/chaos_preempt.py --smoke

# multi-controller chaos: a REAL 2-process jax.distributed pod (gloo
# collectives) shrinks 8 -> 4 through the membership barrier when a
# member is SIGKILLed, regrows on a replacement, survives a DUAL
# SIGKILL of both trainer processes plus a torn newest checkpoint (the
# relaunch must broadcast-agree on the newest VALID one and land the
# reference trajectory), and a socket-transport fleet owner process is
# SIGKILLed mid-gather (zero wrong answers), then drained out by a
# scale-down under load (tools/chaos_multiproc.py)
chaos-multiproc:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH $(PY) tools/chaos_multiproc.py

# the make-verify tier: fewer steps/requests, same assertions. The
# budget covers 3 pod lifetimes x 2 controller processes (each pays
# jax.distributed init + per-world step compiles) + the owner
# subprocesses of the fleet cycle
multiproc-smoke:
	PYTHONPATH=$(CURDIR):$$PYTHONPATH timeout -k 10 780 \
	  $(PY) tools/chaos_multiproc.py --smoke

# multi-chip compile/execute validation on 8 virtual CPU devices
dryrun:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

clean:
	rm -rf distributed_embeddings_tpu/cc/*.so __pycache__ */__pycache__ .jax_cache
