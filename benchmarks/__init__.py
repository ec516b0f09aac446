"""The repo's benchmark: one command per cell and run (see ``run.py``).

Lives under ``benchmarks/`` so that no change to the program can change
the yardstick: traffic generation, metric arithmetic, the table of
peaks, operation counts, the plain reference and the comparison that
decides ``correct`` are all here.
"""
