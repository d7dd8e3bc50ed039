"""Device idle share, chat cells."""
from bench.readers import idle_share as read  # noqa: F401
