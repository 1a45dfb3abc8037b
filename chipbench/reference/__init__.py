"""Plain float32 references of the models the benchmark runs.

Nothing here imports the program. ``decoder.py`` holds the decoder, the
loss and the training recipe; ``dense.py`` and ``moe.py`` hold the
feed-forward block that a configuration file names under ``reference``.
"""
from __future__ import annotations

import importlib


def ffn_module(config: dict):
    """The feed-forward module that the config file names."""
    return importlib.import_module(f"{__name__}.{config['reference']}")
