"""Share of device time in the global scheme's checks, docs cells."""
from bench.readers import abft_check_share as read  # noqa: F401
