"""One adapter module per architecture; see `llama.py` for the contract."""

import importlib


def adapter(arch: str):
    """benchmark/models/<arch>.py, found by a configuration's `arch`."""
    return importlib.import_module(f"{__name__}.{arch}")
