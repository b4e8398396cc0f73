"""Argument parsing, the TPU gate, monitors, the window, percentile
arithmetic, trace capture and reduction, the peaks table, the final line."""
