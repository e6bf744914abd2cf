"""The chip benchmark: harness, yardstick and the files each cell is made
of. Entry point: ``bench/run.py``."""
