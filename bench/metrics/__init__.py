"""One reader per metric: ``<metric name>.py`` with ``read(ctx)``,
returning the value or None when there is nothing to read."""
