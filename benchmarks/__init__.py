"""The benchmark: the yardstick later PRs are measured with and may not edit.

``python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``; see ``PERF.md``.
"""
