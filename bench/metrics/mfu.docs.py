"""Whole-step share of the chip's peak, docs cells."""
from bench.readers import mfu as read  # noqa: F401
