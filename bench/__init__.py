"""The chip benchmark: run one cell with ``python bench/run.py``."""
