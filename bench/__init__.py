"""On-chip serving benchmark: one cell (configuration x traffic mix) per run.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""
