# Developer / CI entry points. The native module is optional at runtime
# (every caller degrades to the pure-Python path) but CI must prove BOTH
# legs: `test-transport` runs the ticket-queue suites with the module
# built and again with CERBOS_TPU_NO_NATIVE=1 so the uds fallback and the
# stdlib codecs stay honest.
PYTHON ?= python3
PYTEST_FLAGS ?= -q -p no:cacheprovider

TRANSPORT_TESTS := tests/test_shm_transport.py tests/test_ipc.py tests/test_latency_budget.py
OVERLOAD_TESTS := tests/test_overload.py
PLAN_TESTS := tests/test_plan_batch.py
ROLLOUT_TESTS := tests/test_rollout.py
PROVENANCE_TESTS := tests/test_provenance.py
# the native-touching suites: codec round-trips, frame rings, truncation fuzz,
# the gRPC listener's wire codec against protobuf (every corruption of a request)
ASAN_TESTS := tests/test_native.py tests/test_shm_transport.py tests/test_wire_codec.py

.PHONY: all native native-asan clean test test-transport test-overload \
	test-plan test-rollout test-provenance test-native-asan lint

all: native

native:
	$(MAKE) -C native PYTHON=$(PYTHON)

clean:
	$(MAKE) -C native clean

# tier-1: the full fast suite (slow-marked tests excluded)
test: native
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ $(PYTEST_FLAGS) -m 'not slow'

# both transport legs: shm granted (native present) and uds fallback
# (native disabled) — the second leg must PASS, not skip-collapse, because
# the suites parametrize/guard on native availability themselves.
test-transport: native
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest $(TRANSPORT_TESTS) $(PYTEST_FLAGS)
	JAX_PLATFORMS=cpu CERBOS_TPU_NO_NATIVE=1 $(PYTHON) -m pytest $(TRANSPORT_TESTS) $(PYTEST_FLAGS)

# overload suite on both codec legs: admission refusals ride the ERR-frame
# path through the native shm codec when present, and through the uds
# marshal fallback when it is not — both must carry pclass + retry intact.
test-overload: native
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest $(OVERLOAD_TESTS) $(PYTEST_FLAGS)
	JAX_PLATFORMS=cpu CERBOS_TPU_NO_NATIVE=1 $(PYTHON) -m pytest $(OVERLOAD_TESTS) $(PYTEST_FLAGS)

# batched PlanResources suite (-m plan_batch) on both codec legs: plan
# refusals surface through the same reply codec as check refusals, so the
# chaos leg (plan shed loses zero check requests) must hold with the
# native shm codec present and with the uds marshal fallback.
test-plan: native
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest $(PLAN_TESTS) $(PYTEST_FLAGS) -m plan_batch
	JAX_PLATFORMS=cpu CERBOS_TPU_NO_NATIVE=1 $(PYTHON) -m pytest $(PLAN_TESTS) $(PYTEST_FLAGS) -m plan_batch

# safe-rollout chaos drills on both codec legs: the epoch stamp crosses
# the ticket queue inside STATUS/reply frames, so the mixed-epoch and
# bounded-skew invariants must hold with the native shm codec present and
# with the uds marshal fallback.
test-rollout: native
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest $(ROLLOUT_TESTS) $(PYTEST_FLAGS) -m rollout
	JAX_PLATFORMS=cpu CERBOS_TPU_NO_NATIVE=1 $(PYTHON) -m pytest $(ROLLOUT_TESTS) $(PYTEST_FLAGS) -m rollout

# decision-provenance suite on both codec legs: the winning-rule column
# crosses the ticket queue inside reply frames (native codec v2 and the
# marshal fallback), so rule attribution must survive both encodings.
test-provenance: native
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest $(PROVENANCE_TESTS) $(PYTEST_FLAGS) -m provenance
	JAX_PLATFORMS=cpu CERBOS_TPU_NO_NATIVE=1 $(PYTHON) -m pytest $(PROVENANCE_TESTS) $(PYTEST_FLAGS) -m provenance

# ASan/UBSan leg: rebuild the native module instrumented, run the suites
# that exercise the C++ codec/ring paths (incl. the truncation fuzzers),
# then drop the instrumented .so so ordinary runs don't need the preload.
# python itself isn't ASan-built, so libasan must be preloaded; interpreter-
# level allocations are out of scope, hence detect_leaks=0.
ASAN_LIB := $(shell gcc -print-file-name=libasan.so)

native-asan:
	$(MAKE) -C native asan PYTHON=$(PYTHON)

test-native-asan: native-asan
	JAX_PLATFORMS=cpu LD_PRELOAD=$(ASAN_LIB) \
		ASAN_OPTIONS=detect_leaks=0:abort_on_error=1 \
		$(PYTHON) -m pytest $(ASAN_TESTS) $(PYTEST_FLAGS)
	$(MAKE) -C native clean

# repo-wide static hygiene (satellite of the analyzer PR): ruff config
# lives in pyproject.toml so editors and CI agree on one rule set.
lint:
	ruff check .
