"""One module per architecture, named by each configuration file's
``"arch_module"`` key and imported as ``bench.arch.<arch_module>``.

A module holds everything of the benchmark that depends on the block:
plain functions over ``d``, the sizes it reads from a configuration. It
imports nothing of the program at import time.

- ``dims(conf)``: the configuration's sizes under the module's own names,
  with ``vocab_size`` among them (the traffic draws its tokens below it);
- ``param_spec(d)``: ``(path, shape, dtype, init scale)`` of every weight,
  in the program's tree and a fixed order; the harness checks it against
  the program's own tree;
- ``make_params(d, seed)``: those weights from the seed
  (``common.make_weights``);
- ``Reference(d, params).logits_at(tokens, positions)``: the plain float32
  reference's logits at chosen positions of padded rows;
- ``program_arch(conf, d)``: the program's ``ArchConfig`` for these sizes,
  raising where the program cannot run what the configuration states;
- ``flops_per_token(d, ctx)`` and ``prompt_flops(d, start, end)``: forward
  FLOPs (``model_mfu``);
- ``moe_ffn_work(d, forwards, lo_bits, group)``: least FLOPs and bytes of
  the expert FFN over ``moe_forward`` records (``moe_ffn_roofline``).

What no architecture owns is in ``common``.
"""
from __future__ import annotations

import importlib
from pathlib import Path
from types import ModuleType
from typing import Dict

#: What every architecture module provides.
PROVIDES = ("dims", "param_spec", "make_params", "Reference", "program_arch",
            "flops_per_token", "prompt_flops", "moe_ffn_work")


def load(conf: Dict) -> ModuleType:
    """The architecture module the configuration names."""
    name = conf.get("arch_module")
    if not name:
        raise KeyError(f"configuration {conf.get('name')!r} names no "
                       "'arch_module' (a module of bench/arch/)")
    try:
        mod = importlib.import_module(f"{__name__}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"{__name__}.{name}":
            raise
        known = sorted({p.stem for d in __path__ for p in Path(d).glob("*.py")
                        if p.stem not in ("__init__", "common")})
        raise KeyError(f"configuration {conf.get('name')!r} names "
                       f"arch_module {name!r}, and bench/arch/ has no such "
                       f"module (known: {known})") from None
    missing = [f for f in PROVIDES if not hasattr(mod, f)]
    if missing:
        raise AttributeError(f"architecture module {name!r} lacks {missing}")
    return mod
