"""Chip benchmark of the HeMT runtime: one cell is one model configuration
under one traffic mix, as ``BENCHMARK.json`` at the repository root names
them. ``python3 chipbench/run.py --workload <cell> ...`` runs one cell once.
"""
