# Build/test entry points (the reference drove everything through
# make; here the Python path needs no compilation, so targets wrap the
# native builds, test tiers, docs generation, and deploy bundle).
#
# Every test/check target runs on the CPU backend (JAX_PLATFORMS=cpu).
# `chip-smoke` needs a TPU and fails without one; from a sandbox
# without a chip, run it through the chip tool (README "Testing").
# Speed is measured by perfbench/run.py on the chip (PERF.md).

PY      ?= python
CPUENV  := JAX_PLATFORMS=cpu
XLA8    := XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: all test nightly examples lint lint-check libs predict perl \
	docs dryrun chip-smoke cache-check serving-check sync-check data-check \
	passes-check telemetry-check decode-check race-check \
	effects-check \
	shard-check profiling-check numerics-check coldstart-check \
	fleet-check quant-check elastic-check clean

all: libs test

# full unit suite on the virtual 8-device CPU mesh
test:
	$(CPUENV) $(PY) -m pytest tests/ -q --ignore=tests/nightly

# distributed tier: multi-process workers on one host (CI pattern)
nightly:
	$(CPUENV) $(PY) tools/launch.py -n 2 --launcher local \
	    $(PY) tests/nightly/dist_sync_kvstore.py
	$(CPUENV) $(PY) tools/launch.py -n 2 --launcher local \
	    $(PY) tests/nightly/dist_async_kvstore.py
	$(CPUENV) $(PY) tools/launch.py -n 2 --launcher local \
	    $(PY) tests/nightly/dist_fused_module.py
	$(CPUENV) $(PY) tools/launch.py -n 2 --launcher local \
	    $(PY) tests/nightly/dist_fault_detect.py
	$(CPUENV) $(PY) tools/launch.py -n 2 --launcher local \
	    $(PY) tests/nightly/dist_push_overlap.py
	$(CPUENV) $(PY) tools/launch.py -n 2 --launcher local \
	    $(PY) tests/nightly/dist_run_steps.py
	$(CPUENV) $(PY) tests/nightly/multi_kvstore_types.py

examples:
	$(CPUENV) $(PY) -m pytest tests/test_examples.py -q

lint:
	$(CPUENV) $(PY) -m pytest tests/test_lint.py tests/test_docs.py -q

# framework-native analyzer gate: mxlint over the tree (baseline-aware),
# self-hosting pass, and a seeded-violation sanity check. Stdlib-only —
# no CPU guard needed (the CLI never imports jax).
lint-check:
	bash ci/check_lint.sh

# native libraries: embeddable core C API + predict-only ABI +
# IO cores (recordio reader, JPEG decode pool, dependency engine)
libs:
	$(CPUENV) $(PY) -c "from mxnet_tpu import native; \
	    print(native.build_core_lib()); \
	    print(native.build_predict_lib()); \
	    native.get_lib(); native.get_lib_imgdec(); \
	    native.get_lib_engine(); print('io/engine libs OK')"

# amalgamated single-file predict bundle -> build/
predict:
	$(CPUENV) $(PY) tools/amalgamation.py --out build

# perl XS binding over the predict C ABI (compiled-and-run smoke)
perl:
	$(CPUENV) $(PY) -m pytest tests/test_perl_binding.py -q

docs:
	$(CPUENV) $(PY) tools/gen_env_docs.py

# executor-cache tier: static no-jit-in-per-step guard + cache tests
cache-check:
	$(CPUENV) bash ci/check_exec_cache.sh

# serving tier: the test suite (bucketing, flush policy, backpressure,
# deadlines, zero-retrace steady state)
serving-check:
	$(CPUENV) bash ci/check_serving.sh

# pipelined-loop tier: the steady-state fit loop performs blocking
# fetches only at log intervals, never per step
sync-check:
	$(CPUENV) $(PY) ci/check_no_perstep_sync.py

# input-pipeline tier: steady-state fit over the mxnet_tpu.data stack
# has zero input stalls with device prefetch on, and a run killed
# mid-epoch auto-resumes with a bit-identical remaining batch stream
data-check:
	$(CPUENV) $(PY) ci/check_input_stall.py

# graph-pass tier: per-pass parity tests + runtime A/B gate (pipeline
# shrinks the executed graph at 1e-6 parity, zero steady-state retraces,
# isomorphic builds share one compiled program)
passes-check:
	$(CPUENV) bash ci/check_passes.sh

# telemetry tier: test suite + runtime gates (every serving request
# correlated submit->reply, /metrics + /statusz agree with in-process
# snapshots, always-on tracing within 3% of step time, flight record
# on an injected fault)
telemetry-check:
	$(CPUENV) bash ci/check_telemetry.sh

# decode tier: test suite + runtime gates (zero retraces over a
# >=64-step continuous decode with mid-stream admission/eviction/
# preemption, greedy parity vs an unbatched reference loop, page-pool
# exhaustion preempts instead of crashing)
decode-check:
	$(CPUENV) bash ci/check_decode.sh

# effects + protocol gate: MX010-MX013 clean tree with no baseline,
# then one seeded violation per rule (jit impurity, use-after-donate,
# unordered digest iteration, orphaned wire op) each caught with
# exactly its own code. Stdlib-only — no CPU guard needed.
effects-check:
	bash ci/check_effects.sh

# concurrency race gate: MX006-MX008 clean tree with no baseline, a
# seeded lock-order inversion caught both statically (MX007) and by
# the runtime witness (LockOrderViolation instead of deadlock), and a
# serving+decoding+data+telemetry soak that finishes deadlock-free
# under MXNET_LOCK_WITNESS=raise
race-check:
	$(CPUENV) bash ci/check_concurrency.sh

# sharding tier: test suite + runtime gates (bitwise training parity
# across unsharded / dp-only / dp*tp*fsdp plans on exact arithmetic,
# fsdp per-device storage <= 1/2 replicated, zero steady-state
# retraces, pre-trace rejection of non-dividing explicit specs) on 8
# virtual devices
shard-check:
	$(CPUENV) $(XLA8) bash ci/check_sharding.sh

# profiling tier: test suite + runtime gates (deviceStats covers every
# cached executable after warmup, zero steady-state traces/records
# under instrumentation, calibrated_cost measured-backed for served
# graphs, HBM pre-flight warns/raises before any trace)
profiling-check:
	$(CPUENV) bash ci/check_profiling.sh

# numerics tier: test suite + runtime gates (injected NaN detected at
# the seeded step within one drain interval, attributed to the op fed
# by the poisoned parameter, durable flight record, host-sync budget
# unchanged with numerics on)
numerics-check:
	$(CPUENV) bash ci/check_numerics.sh

# coldstart tier: disk exec-cache + bundle test suite, then the
# three-subprocess runtime gate (warm snapshot -> fresh-interpreter
# restore with zero traces, zero compiles, bit-identical outputs;
# tampered bundle rejected)
coldstart-check:
	$(CPUENV) bash ci/check_coldstart.sh

# fleet tier: control-plane test suite, then the three-replica
# runtime gate (one bundle -> 0 traces/0 compiles per replica;
# SIGKILL + graceful drain both zero-loss and bit-identical)
fleet-check:
	$(CPUENV) bash ci/check_fleet.sh

# quantized-serving tier: int8 KV-page test suite, then the runtime
# gates (greedy top-1 agreement >= 0.9 vs float32, measured pool
# capacity >= 1.9x, zero steady-state retraces at int8, a
# quantize="int8" bundle restored in a fresh process at 0 traces /
# 0 compiles, stripped quantization record refused)
quant-check:
	$(CPUENV) bash ci/check_quant.sh

# elastic-training tier: reshard/re-key test suite, then the runtime
# gates (one of two subprocess workers SIGKILLed mid-epoch by its own
# fault injector, survivor finishes bitwise equal to the
# uninterrupted reference with every example consumed exactly once;
# 1→2 re-grow at zero example loss and zero steady-state retraces)
elastic-check:
	$(CPUENV) bash ci/check_elastic.sh

# multi-chip sharding dryrun (DP / SP+TP / PP / EP) on 8 virtual devices
dryrun:
	$(PY) __graft_entry__.py

# the quickest proof the train and decode paths start on the chip: one
# process, one chip; `make chip-smoke ARGS="--chips 4"` on a four-chip
# host, `ARGS="--size tiny"` with JAX_PLATFORMS=cpu for the rehearsal
chip-smoke:
	$(PY) chip_smoke.py $(ARGS)

clean:
	rm -rf build __pycache__ */__pycache__ */*/__pycache__
	rm -f native/libmxtpu_c.so native/libmxtpu_predict.so
