"""Compilations inside the window, docs cells."""
from bench.readers import compiles_in_window as read  # noqa: F401
