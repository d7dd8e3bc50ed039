"""Whole-step share of the chip's peak, chat cells."""
from bench.readers import mfu as read  # noqa: F401
