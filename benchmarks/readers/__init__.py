"""One file a reader, found by the name a per-layer metric's file gives.
``read(evidence, params)`` returns the number, or None where the run
left nothing for it to read (the metric is then left out of the line)."""
