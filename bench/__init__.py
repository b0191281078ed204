"""Chip benchmark of the RAG serving path: ``python bench/run.py --help``."""
