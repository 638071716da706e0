"""Plain float32 references, one module per model family, found by the
``family`` key of a configuration file.  They import nothing of the
program under test."""
