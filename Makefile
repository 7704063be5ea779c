# Build-system parity with the reference's Makefile (SURVEY.md §2 "Build
# system"): the reference compiles its C++ TF ops into a shared object;
# here the only ahead-of-time artifact is the C++ parser/dedup extension
# (the TPU compute kernels are JIT-compiled by XLA/Pallas at runtime).
#
#   make            build the parser extension
#   make test       run the test suite
#   make bench      run the benchmark (one JSON line)
#   make bench-host standalone host-only 1/2/4-worker sweep of the
#                   parallel data plane (no device needed)
#   make bench-predict  standalone predict line: cross-file streaming
#                   scorer trials + its host_threads 1/2/4 sweep
#   make bench-vocab    admission-path overhead: train e2e at
#                   vocab_mode=admit vs fixed (target <= 5% cost)
#   make bench-wire standalone wire-format sweep: padded-wide vs
#                   packed-wide vs packed-narrow on h2d_only and e2e,
#                   with bytes/example on the wire
#   make bench-memory  device-memory ledger profile: bytes/row,
#                   planner-vs-ledger and peak-vs-model ratios off a
#                   real train run, serve reload spike off a real
#                   hot reload
#   make bench-fleet  serving-fleet latency line: client-side p50/p99
#                   and req/s through the failover proxy at 1 vs 3
#                   replicas (real child processes), scaling factor
#                   pinned as throughput_x
#   make lint       fmlint whole-program pass (R000-R017) over
#                   fast_tffm_tpu/, tools/, run_tffm.py, bench.py;
#                   writes the machine-readable findings artifact to
#                   .fmlint_cache/findings.json and prints per-rule
#                   wall time (--profile)
#   make chaos      fault-injection soak scenarios on CPU (fmchaos)
#   make stream-soak  the streaming run-mode scenarios standalone
#                   (torn writes / SIGTERM+resume / truncation)
#   make serve      run the online scorer on sample.cfg (needs a
#                   published checkpoint: fmckpt publish, or a stream
#                   trainer with publish_interval_seconds)
#   make serve-soak the serving chaos scenario standalone (concurrent
#                   requests across a hot reload, bit-identical to
#                   batch predict)
#   make slo-soak   the closed-loop SLO scenario standalone: gated
#                   stream trainer + live writer + concurrent serving
#                   + a poisoned burst the publish gate must catch
#   make grow-soak  the elastic GROW scenarios standalone: SIGKILL a
#                   worker, shrink, admit a --join replacement back to
#                   full membership (bit-identical to an uninterrupted
#                   control), plus the joiner-dies-mid-rendezvous leg
#   make bench-multihost  multi-host scaling-efficiency row: real 1-
#                   and 2-process localhost clusters, per-worker rate
#   make bench-diff OLD=a.json NEW=b.json  per-row regression diff of
#                   two bench artifacts (exit 1 past TOLERANCE=0.85)
#   make anatomy METRICS=path.jsonl  clock-aligned cross-rank step
#                   anatomy report from a traced run's metrics shards
#                   (fmtrace --anatomy; needs trace_spans = true)
#   make clean

# The parser binary carries its build key in its name
# (_parser.<hash of source, flags, CPU>.so; fast_tffm_tpu/data/cparser.py),
# so the loader's own builder IS the build rule: make and a first run
# cannot disagree about the flags or the name, and a binary copied in
# from another machine is never the one that gets loaded.
all: parser

parser:
	python -m fast_tffm_tpu.data.cparser

test: parser
	python -m pytest tests/ -q

bench: parser
	python bench.py

bench-host: parser
	JAX_PLATFORMS=cpu python bench.py --host-sweep

bench-predict: parser
	python bench.py --predict

bench-vocab: parser
	python bench.py --vocab

bench-wire: parser
	python bench.py --wire

bench-memory: parser
	JAX_PLATFORMS=cpu python bench.py --memory

bench-fleet: parser
	JAX_PLATFORMS=cpu python bench.py --fleet

lint:
	python -m tools.fmlint --profile --json-out .fmlint_cache/findings.json

chaos: parser
	JAX_PLATFORMS=cpu python -m tools.fmchaos

stream-soak: parser
	JAX_PLATFORMS=cpu python -m tools.fmchaos stream-soak stream-truncate

serve: parser
	python run_tffm.py serve sample.cfg

serve-soak: parser
	JAX_PLATFORMS=cpu python -m tools.fmchaos serve-soak

slo-soak: parser
	JAX_PLATFORMS=cpu python -m tools.fmchaos slo-soak

grow-soak: parser
	JAX_PLATFORMS=cpu python -m tools.fmchaos kill-then-grow grow-joiner-dies

bench-multihost: parser
	JAX_PLATFORMS=cpu python bench.py --multihost

TOLERANCE ?= 0.85
bench-diff:
	python bench.py --compare $(OLD) $(NEW) --tolerance $(TOLERANCE)

METRICS ?= metrics.jsonl
anatomy:
	python -m tools.fmtrace --anatomy $(METRICS) $(wildcard $(METRICS).p*)

clean:
	rm -f fast_tffm_tpu/data/_parser*.so

.PHONY: all parser test bench bench-host bench-predict bench-vocab bench-wire bench-memory bench-fleet bench-multihost bench-diff anatomy lint chaos stream-soak serve serve-soak slo-soak grow-soak clean
