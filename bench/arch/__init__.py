"""Architecture bindings: all the benchmark knows of one model family.

A configuration file's `model_type` (the key of the published
`config.json`) names its binding, loaded by path from the checkout:

  bench/arch/<model_type>.py  or  bench/arch/<model_type>/__init__.py

An unknown `model_type` is an error that names the file looked for; there
is no default architecture.  Adding an architecture is adding its binding
and its configuration files: no file of the harness changes.

A binding provides, each a function of the configuration dict `config`
as read from `bench/configs/<name>.json`:

  model_config(config) -> ModelConfig
      the program's model configuration (`repro.configs.base`)
  make_weights(config, key)
      the weights in the reference's own layout, drawn from `key` on the
      device (traced under `jax.jit`; use `bench/lib/weights.py`'s draws)
  to_program(w, config)
      those weights in the program's parameter layout, exactly: the
      harness checks the result's tree, shapes and dtypes against the
      program's `init_params`
  logits_at(w, config, tokens, rows, *, quant=None)
      (len(rows), vocab) float32 logits of the sequence `tokens` at
      positions `rows`: the plain float32 reference of `correct`, written
      from the published description and importing nothing of the
      program.  With `quant` ("int8", "fp8"; `weights.quantize`) the same
      pass in that lower precision: the control
  decode_token_flops(config, ctx), prefill_flops(config, n)
      model FLOPs of one token decoded against `ctx` positions and of one
      prompt of `n` tokens (`step_mfu.chat` adds them up)
  KERNELS
      a tuple of the names of the Pallas kernels that this architecture's
      programs run and whose device events the trace reader attributes,
      summed per program (`Program.kernels`).  An op of another name is
      read as any other op.  A program's kind (decode, prefill) comes from
      its name, not from the kernels it holds, so a decode step that runs
      none of them is still a decode step
  <kernel>_cost(config, ...) -> {"flops": ..., "bytes": ...}
      for each kernel in KERNELS (the loader refuses a binding without
      one), the least operations and bytes of one call, for its roofline
      share (`bench/metrics/<kernel>_roofline.py`)
  TINY
      {"config": model keys of a tiny CPU size, "cases": {name: overrides},
       "atol": tolerance of the engine against the reference there,
       "why": the reason for that tolerance}; the CPU tests serve every
      case through the engine and compare it with `logits_at`
"""

from __future__ import annotations

import importlib.util
import os
import sys
import zlib
from types import ModuleType
from typing import Any, Dict, List

from bench.lib.spec import ROOT

PROVIDES = (
    "model_config",
    "make_weights",
    "to_program",
    "logits_at",
    "decode_token_flops",
    "prefill_flops",
    "KERNELS",
    "TINY",
)


def _arch_dir(root: str) -> str:
    return os.path.join(root, "bench", "arch")


def binding(model_type: str, *, root: str = ROOT) -> ModuleType:
    """The binding of `model_type` under `<root>/bench/arch`, loaded once
    per process."""
    base = os.path.join(_arch_dir(root), model_type)
    if os.path.isfile(os.path.join(base, "__init__.py")):
        path, search = os.path.join(base, "__init__.py"), [base]
    elif os.path.isfile(base + ".py"):
        path, search = base + ".py", None
    else:
        raise FileNotFoundError(
            f"no binding for model_type {model_type!r}: looked for "
            f"{os.path.relpath(base, root)}.py and {os.path.relpath(base, root)}/__init__.py"
        )
    # a name fixed by the path, so that each binding loads once and a
    # package's relative imports resolve
    name = f"bench_arch_{zlib.crc32(os.path.abspath(path).encode()):08x}_{model_type}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path, submodule_search_locations=search)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    missing = [p for p in PROVIDES if not hasattr(mod, p)]
    missing += [f"{k}_cost" for k in getattr(mod, "KERNELS", ()) if not hasattr(mod, f"{k}_cost")]
    if missing:
        del sys.modules[name]
        raise AttributeError(f"binding {os.path.relpath(path, root)} lacks {missing}")
    return mod


def for_config(config: Dict[str, Any], *, root: str = ROOT) -> ModuleType:
    """The binding that `config["model_type"]` names."""
    if "model_type" not in config:
        raise KeyError(f"configuration {config.get('name')!r} has no model_type")
    return binding(config["model_type"], root=root)


def names(*, root: str = ROOT) -> List[str]:
    """Every model_type with a binding under `<root>/bench/arch`."""
    found = []
    for entry in sorted(os.listdir(_arch_dir(root))):
        path = os.path.join(_arch_dir(root), entry)
        if entry.endswith(".py") and entry != "__init__.py":
            found.append(entry[:-3])
        elif os.path.isfile(os.path.join(path, "__init__.py")):
            found.append(entry)
    return found
