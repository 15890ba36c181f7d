"""The serving benchmark: `python3 bench/run.py --workload <name> ...` (see run.py)."""
