"""Chip benchmark of the serving stack: data files (configurations,
traffic mixes, peaks) read by one harness, a per-metric reader each,
and a plain reference that decides ``correct``.  ``bench/run.py`` is
the entry point; nothing here is imported by the program."""
