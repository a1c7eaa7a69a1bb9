# Developer entrypoints (kubebuilder-style targets, reference Makefile parity).

IMG ?= gcr.io/PROJECT/tpu-inference-gateway:latest

.PHONY: test test-e2e chaos native native-asan native-tsan loadgen sim sim-check metrics-docs top usage-check lint typecheck docker-build install deploy undeploy fmt

test:            ## unit + integration tests (CPU, virtual 8-device mesh)
	python -m pytest tests/ -q -m "not e2e"

lint:            ## mechanical layer (ruff, when installed) + the repo-invariant linter (incl. the concurrency rules; --timings shows which rule is slow)
	@if command -v ruff >/dev/null 2>&1; then ruff check .; \
	else echo "ruff not installed — mechanical layer served by the invariant linter's mech-* fallback rules"; fi
	python -m llm_instance_gateway_tpu.lint --timings

typecheck:       ## scoped mypy gate over the contract-bearing core (mypy.ini)
	@if command -v mypy >/dev/null 2>&1; then mypy --config-file mypy.ini; \
	else echo "mypy not installed — SKIPPING the scoped typecheck gate (loud skip, not a pass)"; fi

native-asan:     ## sanitized native build: ASan/UBSan libligsched + hostile-snapshot FFI fuzz + ctypes parity
	python tools/native_asan_check.py

native-tsan:     ## thread-sanitized native build: concurrent pick_many vs snapshot swaps under the _call_lock protocol + lock-free const picks
	python tools/native_tsan_check.py

test-e2e:        ## full local stack: server + gateway + sidecar as processes
	python -m pytest tests/test_e2e_local.py -q -m e2e

chaos:           ## seeded fault-injection scenarios vs the in-process stack
	python tools/chaos.py --seed 0 --scenario all

native:          ## build the C++ scheduler hot path
	$(MAKE) -C llm_instance_gateway_tpu/native

loadgen:         ## gateway load rig (200 fake pods x 5 adapters)
	python -m llm_instance_gateway_tpu.gateway.loadgen --requests 10000

sim:             ## routing-policy simulation sweep
	python -m llm_instance_gateway_tpu.sim.run --qps 20 30 --policies random production

sim-check:       ## deterministic twin-calibration scenario: observable recovery + committed TWIN_CALIBRATION.json reproduction + knee sanity
	env JAX_PLATFORMS=cpu python -m llm_instance_gateway_tpu.sim.run --twin-scenario

metrics-docs:    ## regenerate docs/METRICS.md from the metric registry
	python tools/gen_metrics_docs.py docs/METRICS.md

top:             ## one-shot lig-top render of a running gateway's /debug/usage
	python tools/lig_top.py --once --url $${LIG_URL:-http://localhost:8081}

usage-check:     ## invariant lint + typecheck + sanitized native builds + attribution conservation + noisy-neighbor + fairness + placement + multipool enforcement + statebus + fleet obs + profiler + decode levers + concurrency harness + KV economy + capacity twin + docs currency
	$(MAKE) lint
	$(MAKE) typecheck
	$(MAKE) native-asan
	$(MAKE) native-tsan
	$(MAKE) sim-check
	python -m pytest tests/test_usage.py tests/test_fairness.py tests/test_placement.py tests/test_multipool.py tests/test_statebus.py tests/test_fleetobs.py tests/test_profiler.py tests/test_decode_levers.py tests/test_kv_ledger.py tests/test_kvobs.py tests/test_capacity.py tests/test_sim.py tests/test_metrics_docs.py tests/test_lint.py tests/test_concurrency.py -q
	python tools/chaos.py --seed 0 --scenario noisy_neighbor
	python tools/chaos.py --seed 0 --scenario adapter_flood
	python tools/chaos.py --seed 0 --scenario cold_start_storm
	python tools/chaos.py --seed 0 --scenario replica_partition
	python tools/chaos.py --seed 0 --scenario saturation_ramp

docker-build:    ## build the framework image
	docker build -t $(IMG) .

install:         ## install CRDs
	kubectl apply -f deploy/crds/

deploy: install  ## deploy gateway + model-server pool
	kubectl apply -f deploy/gateway/ -f deploy/model-server/

undeploy:
	kubectl delete -f deploy/gateway/ -f deploy/model-server/ --ignore-not-found
