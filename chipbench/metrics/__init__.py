"""Per-layer metrics: ``<name>.py`` holds ``read(readings)``, which returns
the metric's value, or None where the run has nothing to read it from."""
