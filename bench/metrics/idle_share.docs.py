"""Device idle share, docs cells."""
from bench.readers import idle_share as read  # noqa: F401
