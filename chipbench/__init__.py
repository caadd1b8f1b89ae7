"""The chip benchmark of EF-BV training: ``python3 chipbench/run.py --help``."""
