"""Distributed correctness on an 8-device host mesh (subprocess so the
device-count flag applies before jax initialises)."""
import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.real_backend]

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.models import registry
from repro.distributed import sharding as shd
from repro.launch.mesh import make_debug_mesh

mesh = make_debug_mesh(4, 2)
out = {}

# 1. every assigned arch's param specs are mesh-valid (this would raise on
#    a non-divisible sharding) and a reduced train step matches 1-device.
arch = "deepseek-7b"
cfg = configs.reduced(configs.get_config(arch)).replace(
    dtype="float32", num_layers=2)
api = registry.get_model(cfg)
params = api.init(jax.random.PRNGKey(0))
pspecs = shd.param_specs(cfg, params, mesh)
p_sh = shd.to_named(pspecs, mesh)

tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
batch = {"tokens": tok, "labels": tok}

def loss_fn(p, b):
    l, _ = api.loss(p, b)
    return l

ref = float(loss_fn(params, batch))

with jax.set_mesh(mesh):
    b_sh = shd.to_named(shd.data_specs(cfg, batch, mesh), mesh)
    f = jax.jit(loss_fn, in_shardings=(p_sh, b_sh),
                out_shardings=NamedSharding(mesh, P()))
    def run():
        with shd.activation_sharding(("data",), "model"):
            return f(jax.device_put(params, p_sh),
                     jax.device_put(batch, b_sh))
    got = float(run())
out["loss_match"] = abs(got - ref) < 1e-3
out["ref"] = ref
out["got"] = got

# 2. decode with context-parallel KV (seq over model) matches 1-device
_, cache = api.prefill(params, {"tokens": tok}, 64)
lg_ref, _ = api.decode_step(params, cache, tok[:, :1])
with jax.set_mesh(mesh):
    c_sh = shd.to_named(shd.cache_specs(cfg, cache, mesh), mesh)
    t_sh = shd.to_named(shd.token_specs(tok[:, :1], mesh), mesh)
    g = jax.jit(lambda p, c, t: api.decode_step(p, c, t),
                in_shardings=(p_sh, c_sh, t_sh))
    lg_sh, _ = g(jax.device_put(params, p_sh),
                 jax.device_put(cache, c_sh),
                 jax.device_put(tok[:, :1], t_sh))
out["decode_match"] = bool(jnp.max(jnp.abs(lg_sh - lg_ref)) < 1e-3)

# 3. all assigned archs produce valid (constructible) NamedShardings
ok = []
for a in configs.ASSIGNED_ARCHS:
    c = configs.get_config(a)
    specs = shd.param_specs(c, registry.param_specs(c), mesh)
    shd.to_named(specs, mesh)
    ok.append(a)
out["spec_archs"] = len(ok)
print("RESULT:" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def dist_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = _SRC
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT:")]
    assert line, proc.stdout[-2000:]
    return json.loads(line[0][len("RESULT:"):])


def test_sharded_loss_matches_single_device(dist_result):
    assert dist_result["loss_match"], dist_result


def test_context_parallel_decode_matches(dist_result):
    assert dist_result["decode_match"]


def test_all_arch_specs_valid(dist_result):
    assert dist_result["spec_archs"] == 10
