"""Plain reference models, one module each, chosen by the configuration.

A configuration file names the model equations that check it with its
required key ``"reference"``: ``chipbench/refmodels/<reference>.py``. A
module defines, with the leaf names the program's parameters use:

* ``from_config(conf, attn_precision)``: a hashable architecture from the
  configuration file; ``attn_precision`` is the precision of the two
  attention products (None follows the ambient matmul precision);
* ``init_params(arch, key)``: float32 weights from the seed's key, by the
  same draws the program's initializer makes;
* ``logits(arch, params, tokens)``: (B, S) tokens to (B, S, padded vocab)
  float32 logits, the padded columns masked.

A module imports nothing of the program and takes nothing the program
made. There is no default: a dense model checking an expert model would
pass where it should not.
"""
from __future__ import annotations

import importlib
import re
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

DIR = Path(__file__).resolve().parent


def names() -> list:
    return sorted(p.stem for p in DIR.glob("*.py") if p.stem != "__init__")


def load(conf: Dict[str, Any]) -> ModuleType:
    """The reference model the configuration names; a missing key or an
    unknown name is an error."""
    if "reference" not in conf:
        raise ValueError(
            "the configuration names no reference model: add "
            f'"reference": one of {names()}')
    name = conf["reference"]
    if not (isinstance(name, str) and re.fullmatch(r"[a-z0-9_]+", name)
            and (DIR / f"{name}.py").is_file()):
        raise ValueError(f"unknown reference model {name!r}; known: {names()}")
    return importlib.import_module(f"{__name__}.{name}")
