"""Plain references of the pipeline stages, independent of ``repro``."""
