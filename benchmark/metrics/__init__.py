"""One reader per metric, loaded by file name (``<metric name>.py``)."""
