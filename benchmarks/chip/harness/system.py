"""The system under test, built from a configuration file: the
``ServingEngine`` of ``repro.serving.real_backend.build_real_engine``.

This module and ``driver`` are the only ones that import the program.
The program's registry has no public call to register a pair, so a
configuration the program does not list is written into its pair table
here; a configuration the program does list (``registered_as``) is checked
against the file instead, so that a change to the program's cut is caught
rather than measured silently."""
from __future__ import annotations

import importlib.util
import sys

from .models import CHIP_DIR, arch


def model_config(spec: dict):
    """The program's ``ModelConfig`` of a spec, with the fields its
    architecture module gives."""
    from repro.configs.base import ModelConfig
    return ModelConfig(name=spec["name"], **arch(spec).program_fields(spec))


def _shape_of(cfg, fields) -> dict:
    out = {f: getattr(cfg, f) for f in fields}
    if "head_dim" in out:
        out["head_dim"] = cfg.resolved_head_dim
    return out


class ConfigMismatch(RuntimeError):
    pass


def register(conf: dict) -> str:
    """Make the pair of ``conf`` known to the program; returns the arch name
    to pass to ``build_real_engine``."""
    from repro import configs
    specs = (conf["target"], conf["draft"])
    t, d = (model_config(s) for s in specs)
    name = conf["registered_as"]
    if name:
        have = (configs.get_config(name), configs.get_draft_config(name))
        for s, want, got in zip(specs, (t, d), have):
            # every field the spec's module sets
            fields = arch(s).program_fields(s)
            if _shape_of(want, fields) != _shape_of(got, fields):
                raise ConfigMismatch(
                    f"the program registers {name!r} as "
                    f"{_shape_of(got, fields)}, the configuration file says "
                    f"{_shape_of(want, fields)}")
        return name
    configs._PAIRS[conf["name"]] = (t, d)
    return conf["name"]


def weight_generator(name: str):
    path = CHIP_DIR / "weights" / f"{name}.py"
    mod_name = f"chip_weights_{name}"
    if mod_name not in sys.modules:
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[mod_name] = mod
    return sys.modules[mod_name]


def _layout(jax, tree):
    return jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), tree)


def build(conf: dict, mix: dict, seed: int, *, rehearse: bool,
          target_spec: dict, draft_spec: dict):
    """The engine for ``conf`` under ``mix``, its weights replaced by the
    mix's generator's draw from ``seed``.  The engine's own seed (the
    planner's exploration draws) is the mix's, the same for every run, so
    that seeds change the data and not the system's coin flips.  Returns
    (engine, target params, draft params) — the params are the benchmark's
    own arrays."""
    from repro.serving.real_backend import build_real_engine
    arch = register(conf)
    e = mix["engine"]
    engine = build_real_engine(arch, e["policy"], reduced=rehearse,
                               max_batch=e["max_batch"],
                               gamma_max=e["gamma_max"], seed=e["seed"])
    return (engine, *load_weights(engine, mix, seed, target_spec,
                                  draft_spec))


def load_weights(engine, mix: dict, seed: int, target_spec: dict,
                 draft_spec: dict):
    """Free the backend's weights and hand it the mix's generator's draw
    from ``seed``, laid out as the program's own."""
    import jax
    be = engine.backend
    if not be.draft_resident:
        raise RuntimeError("the draft is offloaded: weights cannot change")
    want_t, want_d = _layout(jax, be.tparams), _layout(jax, be.dparams)
    be.tparams = be.dparams = None
    gen = weight_generator(mix["weights"])
    tparams, dparams = gen.generate(target_spec, draft_spec, seed)
    for want, got, what in ((want_t, tparams, "target"),
                            (want_d, dparams, "draft")):
        if _layout(jax, got) != want:
            raise ConfigMismatch(
                f"{what} weights of generator {mix['weights']!r} are not "
                "laid out as the program's")
    be.tparams, be.dparams = tparams, dparams
    jax.block_until_ready((tparams, dparams))
    return tparams, dparams
