"""On-chip benchmark of the geometric search service (see BENCHMARK.json
and PERF.md). ``python -m bench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` runs one cell once."""
