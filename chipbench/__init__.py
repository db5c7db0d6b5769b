"""The chip benchmark: `python3 chipbench/run.py --workload <cell> ...`."""
