"""Program entries a window drives, one module each, found by the name a
traffic mix gives under ``driver``."""
