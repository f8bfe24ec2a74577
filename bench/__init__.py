"""The benchmark of the stencil system: one cell per run, from data files.

`python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
"""
