"""Shared pieces of the yardstick: statistics, peaks, closed forms, the
trace reduction, the manifest loader and the train-window loop."""
