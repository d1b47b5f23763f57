"""Chip benchmark of the served path: cells, traffic, references and readers."""
