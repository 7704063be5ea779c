# Build-system parity with the reference's Makefile (SURVEY.md §2 "Build
# system"): the reference compiles its C++ TF ops into a shared object;
# here the only ahead-of-time artifact is the C++ parser/dedup extension
# (the TPU compute kernels are JIT-compiled by XLA/Pallas at runtime).
#
#   make            build the parser extension
#   make test       run the test suite
#   make lint       fmlint whole-program pass (R000-R017) over
#                   fast_tffm_tpu/, tools/, run_tffm.py;
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

METRICS ?= metrics.jsonl
anatomy:
	python -m tools.fmtrace --anatomy $(METRICS) $(wildcard $(METRICS).p*)

clean:
	rm -f fast_tffm_tpu/data/_parser*.so

.PHONY: all parser test anatomy lint chaos stream-soak serve serve-soak slo-soak grow-soak clean
