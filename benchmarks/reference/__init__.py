"""Plain references, one module per architecture, found by the name a
configuration file gives under ``reference``."""
