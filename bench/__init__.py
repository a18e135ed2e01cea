"""The chip benchmark of this repository: ``BENCHMARK.json`` names its
cells, configurations and metrics; ``bench/run.py`` runs one cell once."""
