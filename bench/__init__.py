"""The benchmark: one command (``run.py``), its harness and yardstick,
and the configurations, traffic mixes and metric readers it finds by
name.  Nothing here is part of the program under test."""
