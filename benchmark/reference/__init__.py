"""Plain references, one module per architecture family, found by the
``reference`` key of a configuration file."""
