"""Plain references, one module each, found by the name a configuration
gives under ``reference``."""

import importlib


def load_reference(cfg: dict):
    return importlib.import_module(f"bench.reference.{cfg['reference']}")
