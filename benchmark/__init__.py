"""The benchmark of minbft-tpu: BENCHMARK.json's harness and all its data.

One process, one cell: ``python3 -m benchmark.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  Everything that belongs to one configuration,
one traffic mix, one per-layer metric, one kernel or one signature scheme is
a file of its own that the harness finds by the name in BENCHMARK.json or in
the configuration (``configs/``, ``traffic/``, ``layer_metrics/``,
``kernels/``, ``verifiers/``, ``peaks.json``); a later PR adds files and
entries and edits none.  PERF.md describes the layers and the cells.
"""
