"""The chip benchmark of patrol_tpu: see run.py and PERF.md."""
