"""One module per way of driving the system under test, found by the
``driver`` key of a traffic file. A driver exposes ``run(bench)``."""
