"""Compilations inside the window, chat cells."""
from bench.readers import compiles_in_window as read  # noqa: F401
