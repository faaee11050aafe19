"""Chip benchmark: cells, drivers, metric readers and references, found by name.

Run one cell with ``python chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` on a machine that holds the cell's TPU chips.
"""
