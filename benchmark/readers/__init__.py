"""Readers of per-layer metrics, one module each, found by the `reader`
name in a metric's data file (metrics/<metric>.json). Each has
``read(spec, run) -> number or None``: `spec` is the metric's data file,
`run` what the harness gathered (window times, operations, process CPU
readings, validator-info reports, the daemon's counters, span file and
profile directory). A reader that finds nothing to read returns None
and the metric is left out of the line; it never returns 0 for a share
of a roofline or of a peak."""
