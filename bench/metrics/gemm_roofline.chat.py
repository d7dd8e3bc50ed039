"""Protected GEMM sites' share of their roofline, chat cells."""
from bench.readers import gemm_roofline as read  # noqa: F401
