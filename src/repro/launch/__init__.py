"""Launch helpers: device mesh construction."""
