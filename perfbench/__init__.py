"""The chip benchmark of the unified serve + fine-tune engine: one command
runs one cell once (``python3 perfbench/run.py --workload <cell> ...``)."""
