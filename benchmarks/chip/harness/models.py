"""A configuration file -> the two model specs the harness works with.

A configuration file (``configs/<name>.json``) holds its models' own
(Hugging Face style) keys, and names for the target and, under ``draft``,
for the draft the architecture module that reads them: ``"arch_module":
"<module>"``, the file ``archs/<module>.py``.  A spec is the plain dict of
sizes that module makes (``spec(name, raw)``).  Every spec holds ``name``,
``arch`` (its module's name, set here) and ``vocab`` (the range of the
token ids the traffic draws); its other keys are its module's own.  Specs
key jitted programs, so every value in one is hashable.

An architecture module provides, each of a spec ``s``:

  spec(name, raw) -> dict            the sizes, from the file's keys; raises
                                     ``ValueError`` for a key it does not
                                     compute
  rehearsal(s) -> dict               the CPU rehearsal's cut
  program_fields(s) -> dict          the program's ``ModelConfig`` fields
  param_shapes(s) -> tree            the parameter tree, leaves as shapes
  param_scale(path, s) -> (mean, std)  of the leaf at "a/b/c"
  hidden(s, quant, params, tokens)   the float32 reference: hidden states
  logits(s, quant, params, rows)     and logits (``quant``: int8 control)
  layer_matmul_params(s), prompt_flops(s, P), head_flops(s),
  token_flops(s, keys)               the operation counts
  attention_calls(s, rows)           [(layers, FLOPs, bytes)], one group
                                     per kind of layer, of one kernel call

A module is loaded by file path, once per process, and found again by its
name.  Nothing here imports the program."""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[1]


def load_arch(name: str, root: Path = CHIP_DIR):
    """The architecture module ``<root>/archs/<name>.py``."""
    path = (root / "archs" / f"{name}.py").resolve()
    mod_name = f"chip_arch_{name}"
    mod = sys.modules.get(mod_name)
    if mod is None:
        if not path.is_file():
            raise ValueError(f"no architecture module {path}")
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[mod_name] = mod
    elif Path(mod.__file__).resolve() != path:
        raise ValueError(f"two architecture modules are named {name!r}: "
                         f"{mod.__file__} and {path}")
    return mod


def arch(spec: dict):
    """The architecture module of ``spec``, as loaded for its file."""
    return sys.modules.get(f"chip_arch_{spec['arch']}") or load_arch(
        spec["arch"])


def _spec(name: str, raw: dict, root: Path) -> dict:
    module = raw.get("arch_module")
    if not module:
        raise ValueError(f"{name}: the configuration names no "
                         "'arch_module'")
    return dict(load_arch(module, root).spec(name, raw), arch=module)


def load_config(name: str, root: Path = CHIP_DIR) -> dict:
    """configs/<name>.json -> {"name", "file", "target": spec, "draft": spec,
    "registered_as", "logit_gap_limit"}."""
    path = root / "configs" / f"{name}.json"
    raw = json.loads(path.read_text())
    if raw["name"] != name:
        raise ValueError(f"{path} names itself {raw['name']!r}")
    return {"name": name, "file": str(path),
            "registered_as": raw.get("registered_as"),
            "logit_gap_limit": raw.get("logit_gap_limit"),
            "target": _spec(name, raw, root),
            "draft": _spec(raw["draft"]["name"], raw["draft"], root)}


def rehearsal_spec(spec: dict) -> dict:
    """The CPU rehearsal's cut of a spec, by its module."""
    return dict(arch(spec).rehearsal(spec), arch=spec["arch"])
