"""SimNet chip benchmark: see run.py and PERF.md."""
