"""The benchmark of minbft-tpu: BENCHMARK.json's harness and all its data.

One process, one cell: ``python3 -m benchmark.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.  Everything that belongs to one configuration,
one traffic mix, one per-layer metric, one kernel, one signature scheme or
one kind of fault is a file of its own that the harness finds by the name in
BENCHMARK.json, in the configuration or in the traffic's schedule
(``configs/``, ``traffic/``, ``layer_metrics/``, ``kernels/``,
``verifiers/``, ``faults/``, ``recorded/anchors/``, ``peaks.json``); a later
PR adds files and entries and edits none.  PERF.md describes the layers and the cells.
"""
