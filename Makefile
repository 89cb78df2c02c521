.PHONY: test test-fast bench bench-table6 bench-scenarios bench-serve \
	bench-scaling bench-obs trace-demo lint lint-clock lint-residency \
	lint-assert chaos example

test:            ## full tier-1 suite
	./scripts/test.sh

test-fast:       ## suite minus tests marked slow (QAT training loops)
	./scripts/test.sh --fast

bench:           ## every benchmark section
	PYTHONPATH=src python -m benchmarks.run

bench-table6:    ## MLPerf-Tiny scenario sweep over compiled deployments
	PYTHONPATH=src python -m benchmarks.run --only table6

bench-scenarios: ## scenario sweep, standalone (REPRO_FAST=1 for a quick pass)
	PYTHONPATH=src:. REPRO_FAST=$(REPRO_FAST) python benchmarks/table6_scenarios.py

bench-serve:     ## serving throughput-at-SLO curves over the dynamic batcher
	PYTHONPATH=src:. REPRO_FAST=$(REPRO_FAST) python benchmarks/serve_bench.py

bench-scaling:   ## throughput-at-SLO vs replica count (simulated pool)
	PYTHONPATH=src:. REPRO_FAST=$(REPRO_FAST) python benchmarks/serve_bench.py --scaling

bench-obs:       ## NullTracer overhead assert + FIFO prediction-error table
	PYTHONPATH=src:. REPRO_FAST=$(REPRO_FAST) python benchmarks/obs_bench.py

trace-demo:      ## one traced server run -> Perfetto timeline artifact
	PYTHONPATH=src:. python benchmarks/obs_bench.py --demo

lint: lint-clock lint-residency lint-assert  ## every static check CI runs

lint-clock:      ## no raw stdlib clock reads outside repro.obs.timer
	python scripts/check_no_raw_clock.py

lint-residency:  ## megakernel plans never exceed the VMEM cap (goldens)
	python scripts/check_megakernel_residency.py

lint-assert:     ## no bare asserts in serve/deploy (python -O safety)
	python scripts/check_no_bare_assert.py

chaos:           ## deterministic fault-injection suite, plain and under -O
	PYTHONPATH=src python -m pytest -x -q tests/test_faults.py
	PYTHONPATH=src python -O -m pytest -x -q tests/test_faults.py

example:         ## the end-to-end codesign + compiled-deployment example
	PYTHONPATH=src python examples/mlperf_tiny_codesign.py
