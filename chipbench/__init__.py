"""The on-chip benchmark: the yardstick later PRs are measured with.

One run is ``python3 -m chipbench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout. Everything that
belongs to one configuration, one traffic mix, one job kind or one metric
is a file of its own, found by the name ``BENCHMARK.json`` gives it (see
``catalog.py``); a later PR extends the benchmark by adding files.
"""
