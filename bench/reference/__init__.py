"""Plain float32 references, one module per architecture; they import
nothing of the program.  Each module gives, besides its reference:
``Dims.from_config``, ``init_stacked``, ``train_reference``,
``forward_flops``, ``param_count``, ``program_params`` and
``PROGRAM_KINDS``, so that a configuration of a new architecture needs a
new module here and no edit elsewhere."""

import importlib


def of(config: dict):
    """The module named by the configuration's ``reference`` key."""
    return importlib.import_module(f"bench.reference.{config['reference']}")
