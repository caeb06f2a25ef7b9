"""One module per model family, named as a configuration's model entry
names its ``family``: how the published config's keys map onto the
program's ``ArchConfig`` fields (``arch_fields``), the work one served
token needs (``dims``, ``layer_params``, ``weight_bytes``,
``state_bytes``, ``token_flops``) and the CPU test size (``TINY``).
A new family enters the benchmark as a new module here and its float32
reference under ``bench/reference/``; nothing else changes."""
from __future__ import annotations

import importlib
from types import ModuleType
from typing import Any, Dict


def of(entry: Dict[str, Any]) -> ModuleType:
    """The module of a model entry's family."""
    name = f"{__name__}.{entry['family']}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(f"unknown model family {entry['family']!r}: no "
                         f"module bench/families/{entry['family']}.py") \
            from None

