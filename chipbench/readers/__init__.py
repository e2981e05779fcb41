"""One module per reader.  `read(ctx, params)` takes the run's context (two
snapshots of the daemon's counters that bracket ramp and window, the reduced
trace, the cell's files, what the generator sent) and returns the metric's
value, or None where there is nothing to read."""
