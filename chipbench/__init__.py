"""On-chip serving benchmark: data-driven cells over the continuous-batching
engine, driven by ``python chipbench/run.py --workload <cell> ...``."""
