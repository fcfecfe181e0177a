"""Chip benchmark for the OptiReduce training path (see PERF.md).

``python -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU it is
started on and prints one JSON result line.
"""
