"""One runner a traffic ``kind``, found by name: ``kinds/<kind>.py`` has
``run(ctx) -> common.Result``."""
