# Convenience targets; PYTHONPATH=src is the repo's only install step.
PY := PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH)) python

BASELINE := BENCH_superstep.prev.json
# Interpret-mode CPU timings swing ±30%+ with machine load; the wide default
# catches step-function regressions without flaking on noise (tighten on
# real TPU runs: make bench-check BENCH_THRESHOLD=0.20).
BENCH_THRESHOLD ?= 0.75

.PHONY: test lint bench bench-quick bench-batched bench-dist bench-dynamic \
	bench-checkpoint bench-continuous bench-oocore bench-dopt bench-gate \
	bench-check serve serve-mutate serve-continuous serve-oocore chaos \
	corrupt-drill ci

test:            ## tier-1 suite
	$(PY) -m pytest -x -q

lint:            ## fast critical-rule lint (skips if ruff absent)
	@if command -v ruff >/dev/null 2>&1; then \
	  ruff check .; \
	else \
	  echo "lint: ruff not installed, skipping (pip install -r requirements-ci.txt)"; \
	fi

bench:           ## reference-vs-fused superstep timings -> BENCH_superstep.json
	$(PY) benchmarks/superstep_bench.py

bench-quick:     ## smallest scale only (the CI bench job; batched + dynamic + checkpoint + continuous + verify + oocore + dopt)
	$(PY) benchmarks/superstep_bench.py --quick --batched --mutations \
	  --checkpoint --continuous --verify --oocore --dopt

bench-batched:   ## query-throughput column only (Q in {1,8,32}) + gate
	$(PY) benchmarks/superstep_bench.py --quick --batched
	$(MAKE) bench-gate

bench-dynamic:   ## dynamic-graph column (mutation edges/s, warm-start) + gate
	$(PY) benchmarks/superstep_bench.py --quick --mutations
	$(MAKE) bench-gate

serve:           ## batched query-serving driver (resident graph, q/s report)
	$(PY) -m repro.launch.graph_serve --scale 12 --batch 32 --alg bfs

serve-mutate:    ## mutating serving driver (resident DynamicGraph)
	$(PY) -m repro.launch.graph_serve --scale 12 --batch 32 --alg bfs \
	  --mutate --churn 1.0

bench-checkpoint: ## fault-tolerance column (snapshot overhead, recovery) + gate
	$(PY) benchmarks/superstep_bench.py --quick --checkpoint
	$(MAKE) bench-gate

serve-continuous: ## continuous-batching serving driver (resident ServeSession)
	$(PY) -m repro.launch.graph_serve --scale 12 --batch 32 --alg bfs \
	  --continuous

bench-continuous: ## continuous-batching column (q/s + p99 vs drain) + gate
	$(PY) benchmarks/superstep_bench.py --quick --continuous
	$(MAKE) bench-gate

serve-oocore:    ## out-of-core serving driver (forced HBM budget, tiered engine)
	$(PY) -m repro.launch.graph_serve --smoke --graph uniform --alg bfs \
	  --backend fused --block-e 128 --win-blocks 4 --hbm-budget 45000

bench-oocore:    ## out-of-core column (tiered vs resident, parity + budget) + gate
	$(PY) benchmarks/superstep_bench.py --quick --oocore
	$(MAKE) bench-gate

bench-dopt:      ## direction-optimized column (top-down vs auto BFS edge counters) + gate
	$(PY) benchmarks/superstep_bench.py --quick --dopt
	$(MAKE) bench-gate

chaos:           ## fault-injection drill: crash/recover/replay, parity asserts
	$(PY) -m repro.launch.graph_serve --smoke --chaos --alg bfs \
	  --backend fused

corrupt-drill:   ## silent-corruption drill: every injection detected or masked
	$(PY) -m repro.launch.graph_serve --smoke --corrupt --alg bfs
	$(PY) -m repro.launch.graph_serve --smoke --corrupt --alg sssp

bench-dist:      ## multi-device column (8 forced host devices, quick scale)
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PY) benchmarks/superstep_bench.py --quick --distributed --devices 8 \
	  --out BENCH_superstep_dist.json

bench-gate:      ## diff BENCH_superstep.json vs the baseline (seeds if absent)
	$(PY) scripts/bench_check.py BENCH_superstep.json \
	  --baseline $(BASELINE) --seed-missing --threshold $(BENCH_THRESHOLD)

bench-check: bench
	$(MAKE) bench-gate

# Mirror of .github/workflows/ci.yml for local runs: lint + tier-1 tests,
# then the quick bench and the regression gate.
ci: lint test
	$(MAKE) bench-quick
	$(MAKE) bench-gate
