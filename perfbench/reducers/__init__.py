"""One small reader per kind of per-layer metric: ``reduce(facts, args)``
returns the number, or ``None`` when it finds nothing to read.  ``facts``
holds the runner's host-clock values and counts, the loaded trace (traced
runs), the device's peaks, the configuration and the traffic mix."""
