"""Supernet families, one module each, found by the name a
configuration file gives under ``model.family`` (``harness.family``)."""
