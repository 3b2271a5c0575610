# jylis-tpu build/test targets (reference analog: the upstream Makefile's
# test/build/debug targets, SURVEY.md section 2.8)

PY ?= python

.PHONY: test soak native run clean \
        check-graft ci image compose-smoke smoke3 release \
        lint lint-native sanitize sanitize-threads chaos metrics-smoke \
        model-smoke

# what CI runs per commit (.github/workflows/ci.yml + .circleci/config.yml):
# hermetic on any host. `test` includes the journal suite
# (tests/test_journal.py — append/replay, corruption classes, rotation, and
# a real SIGKILL/restart boot); `lint` is the repo-native static analyzer
# (scripts/jlint — async/thread safety, JAX trace discipline, native/Python
# RESP surface parity, failpoint manifest parity); `sanitize` rebuilds the
# native engine under ASAN+UBSAN with -Werror and re-runs the jax-free
# native test subset; `sanitize-threads` rebuilds it under TSAN and runs
# the multi-threaded engine drive; `chaos` is the tiny fault-injection
# drill smoke.
ci: native lint lint-native test chaos model-smoke check-graft \
    metrics-smoke sanitize sanitize-threads

# the ten jlint passes (1-5, 7-11) + the hygiene rules (broad-except, suppression
# reasons/staleness), against the committed baseline
# (scripts/jlint/baseline.json — every entry justified in-line, stale
# entries fail). The manifest checks (RESP parity, failpoints, metrics,
# codec symmetry, lattice discipline, protocol
# atlas, cross-language RESP semantics) re-extract
# their surfaces on every run and fail on uncommitted drift; regenerate
# with `$(PY) -m scripts.jlint --write-manifest` (then `--write-corpus`
# if the codec or semantics manifest changed) and commit the diff.
# `--budget` fails
# the run past the recorded wall-time bound (scripts/jlint/budget.json);
# lint_findings.json is the machine-readable CI artifact.
lint:
	$(PY) -m scripts.jlint --budget --out lint_findings.json

# clang-tidy over native/ with the committed curated .clang-tidy
# (warnings-as-errors) + the NOLINT-must-carry-a-reason policy; skips
# the tidy step (exit 0, loud message) when clang-tidy is not installed
# — the -Werror build and `make sanitize` still gate the C++ either way
lint-native:
	$(PY) scripts/lint_native.py

# ASAN+UBSAN build of the native engine (-Werror, no recovery) + the
# jax-free native test subset under the sanitizer runtime. jax stays
# un-imported (JYLIS_SANITIZE gates tests/conftest.py): jaxlib's pybind11
# C++ exceptions abort under the preloaded ASAN interceptor.
sanitize:
	g++ -O1 -g -std=c++17 -shared -fPIC -pthread -fsanitize=address,undefined \
	  -fno-sanitize-recover=all -Wall -Wextra -Werror \
	  -o native/libjylis_native_san.so native/*.cpp
	JYLIS_SANITIZE=1 JYLIS_NATIVE_SO=$(abspath native/libjylis_native_san.so) \
	  LD_PRELOAD=$$(g++ -print-file-name=libasan.so) \
	  ASAN_OPTIONS=detect_leaks=0 \
	  UBSAN_OPTIONS=print_stacktrace=1,halt_on_error=1 \
	  $(PY) -m pytest tests/test_native_resp.py tests/test_native_drive.py \
	  -q -p no:cacheprovider

# TSAN build of the native engine + the multi-threaded ServeEngine
# drive (tests/test_native_tsan.py): per-thread engine isolation
# (concurrent full-surface bursts — ctypes drops the GIL, so the C++
# genuinely runs in parallel) and the external-mutex discipline for a
# shared engine (memo install/invalidate, interner compaction under
# ingest). Skips loudly (exit 0) when the toolchain has no libtsan —
# same policy as clang-tidy in lint-native; the same module still runs
# GIL-only in tier-1 either way. jax stays un-imported (JYLIS_SANITIZE),
# as in `sanitize`.
sanitize-threads:
	@tsan=$$(g++ -print-file-name=libtsan.so); \
	if [ "$$tsan" = "libtsan.so" ] || [ ! -e "$$tsan" ]; then \
	  echo "sanitize-threads: libtsan not found on this toolchain — TSAN step skipped"; \
	  echo "(tests/test_native_tsan.py still runs un-instrumented in tier-1)"; \
	  exit 0; \
	fi; \
	set -e; \
	g++ -O1 -g -std=c++17 -shared -fPIC -pthread -fsanitize=thread \
	  -Wall -Wextra -Werror \
	  -o native/libjylis_native_tsan.so native/*.cpp; \
	JYLIS_SANITIZE=1 JYLIS_NATIVE_SO=$(abspath native/libjylis_native_tsan.so) \
	  LD_PRELOAD=$$tsan \
	  TSAN_OPTIONS=halt_on_error=1,second_deadlock_stack=1 \
	  $(PY) -m pytest tests/test_native_tsan.py -q -p no:cacheprovider

# boot a real node with --metrics-port, scrape it, validate the
# Prometheus exposition grammar + presence of every histogram/gauge in
# scripts/jlint/metrics_manifest.json
metrics-smoke:
	JAX_PLATFORMS=cpu $(PY) scripts/metrics_smoke.py

test:
	$(PY) -m pytest tests/ -x -q

# tiny fault-injection drill smoke: a curated subset of the drill
# matrix — dial backoff/reset/timeout drills, an FFI fault served via
# demotion, the CLUSTER metrics surface, the region and bridge drills
# — per commit via `make ci`. The FULL
# {error,sleep,corrupt,drop,crash} x {every registered failpoint}
# matrix runs nightly behind `-m soak`.
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_drill_matrix.py -m chaos -q

# jmodel: bounded explicit-state exploration of the cluster
# protocol (scripts/jmodel). Drives the REAL Cluster handler code over
# an in-memory deterministic network (virtual clock + pipe transport
# through cluster.py's injectable clock/connect seams), enumerating
# delivery schedules — reorder across conns, drop (conn kill),
# duplicate, partition, crash-reboot-from-journal — over the 2-node,
# 3-node and 3-node-2-region configs with state-hash dedup and sleep-set
# partial-order reduction. Asserts, per state: lattice monotonicity,
# held-queue FIFO + bound, dial-backoff monotonicity; at quiescence:
# digest match on every replica, no stranded rtt stamps, nothing in
# flight. The run must cover >= the recorded model_min_states distinct
# states and finish inside model_budget_seconds (both in
# scripts/jlint/budget.json). Deeper sweep nightly via `-m soak`
# (tests/test_model.py); minimized counterexamples replay from
# tests/model/ in tier-1.
model-smoke:
	JAX_PLATFORMS=cpu $(PY) -m scripts.jmodel --smoke --budget

# nightly CI: the long-running real-process churn/crash drills, including
# the SIGKILL-mid-traffic journal recovery soak, the 16-32 node churn
# soak (tests/test_soak_churn_scale.py — kill/rejoin/partition/heal
# under sustained writes, ends digest-matched with zero whole-state
# dumps), the region-churn soak (tests/test_soak_region_churn.py —
# bridge crash/reboot loops at 3 regions, deterministic succession and
# zero dumps after every handover) and the full fault-injection drill
# matrix (tests/test_drill_matrix.py)
soak:
	$(PY) -m pytest tests/ -q -m soak

# build the native codecs explicitly (they also build lazily on first use).
# Same build() as the lazy path, run as a script so jax is not imported:
# it records the sources' sha256 beside the .so, which is how the loader
# decides staleness (content, not mtime)
native:
	$(PY) jylis_tpu/native/__init__.py

run:
	$(PY) -m jylis_tpu

# what the driver does: single-chip compile check + virtual multi-chip dryrun
check-graft:
	JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PY) -c "import jax; jax.config.update('jax_platforms','cpu'); \
	import __graft_entry__ as g; fn, a = g.entry(); \
	jax.jit(fn).lower(*a).compile(); g.dryrun_multichip(8); print('OK')"

# ---- release / deployment (reference analog: Dockerfile:24-36 +
# Makefile:31-49 — static binary in a scratch image + nightly upload; the
# rebuild ships a wheel + container image + 3-node compose cluster) ------

# the release artifact: a wheel with the prebuilt native codec bundled.
# The bundled copy is removed WHETHER OR NOT pip succeeds: a leftover
# would shadow fresh native/ builds (the loader prefers the package-local
# .so).
release: native
	rm -rf build dist && mkdir -p dist
	cp native/libjylis_native.so jylis_tpu/native/
	$(PY) -m pip wheel --no-deps --no-build-isolation -w dist .; \
	  rc=$$?; rm -f jylis_tpu/native/libjylis_native.so; exit $$rc
	@ls -l dist/

image:
	docker build -t jylis-tpu .

# full-product smoke: 3-node compose cluster converges all five types
compose-smoke:
	docker compose up -d --build
	$(PY) scripts/smoke3.py --ports 6379,6380,6381; \
	  rc=$$?; docker compose down; exit $$rc

# the same smoke without a container runtime: 3 local node processes
# (what CI runs in this environment)
smoke3:
	$(PY) scripts/smoke3.py --spawn

clean:
	rm -f native/libjylis_native.so native/libjylis_native.so.srchash \
	  jylis_tpu/native/libjylis_native.so \
	  native/libjylis_native_san.so native/libjylis_native_tsan.so
	rm -rf build dist
	find . -name __pycache__ -type d -exec rm -rf {} +
