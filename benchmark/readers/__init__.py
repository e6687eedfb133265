"""Per-layer metric readers. Each module has ``read(ctx, **params)``;
``ctx`` holds the trace (plain structure, or None), the cell's counters,
its configuration and traffic, the peaks and the chip count. A reader
that finds nothing to read returns None, and the metric is left out."""
