"""Seeded generators of the benchmark: corpora and traffic schedules."""
