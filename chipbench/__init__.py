"""chipbench: the chip benchmark of shared-tensor-tpu (BENCHMARK.json, PERF.md)."""
