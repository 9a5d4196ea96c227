"""Architecture modules (``archs/``), on the CPU.  Run by path:

  JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

Identity: for both configuration files, the specs, the program's fields,
the weight generator's leaves and the counts at published widths, and at
the rehearsal's widths the weights drawn from one seed and the reference's
gaps, are what the harness computed before the modules existed
(``identity_pins.json``).  Extension: a layout that the harness does not
ship, put under a temporary root as one module and one configuration
file, reaches every part of the harness that works per layout.  Refusal:
the dense module refuses the keys of layouts it does not compute."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(CHIP.parents[1] / "src"))

from harness import counts, models, readers, reference, system  # noqa: E402
from harness.peaks import peaks_for  # noqa: E402

PINS = json.loads((CHIP / "tests" / "identity_pins.json").read_text())
CONFIGS = sorted(PINS["configs"])
WHICH = ("target", "draft")


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


# -- identity with the harness before the modules ------------------------------

@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("config", CONFIGS)
def test_specs_fields_and_leaves_are_the_pinned_ones(config, which):
    pin = PINS["configs"][config][which]
    s = models.load_config(config)[which]
    assert s == dict(pin["spec"], arch="dense_decoder")
    assert models.rehearsal_spec(s) == dict(pin["rehearsal_spec"],
                                            arch="dense_decoder")
    fields = models.arch(s).program_fields(s)
    assert fields == pin["program_fields"]
    cfg = system.model_config(s)
    assert cfg.name == s["name"]
    assert {f: getattr(cfg, f) for f in fields} == fields
    gen = system.weight_generator("random")
    plan = [[p, list(shape), "bfloat16", i] for p, shape, i in gen.leaf_plan(s)]
    assert plan == pin["leaves"]
    drawn = jax.eval_shape(lambda: gen._draw(jax.random.key(0), s))
    assert [[_leaf_name(p), list(x.shape), str(x.dtype)] for p, x in
            jax.tree_util.tree_flatten_with_path(drawn)[0]] == [
                leaf[:3] for leaf in pin["leaves"]]


@pytest.mark.parametrize("which", WHICH)
@pytest.mark.parametrize("config", CONFIGS)
def test_counts_are_the_pinned_ones(config, which):
    pin = PINS["configs"][config][which]["counts"]
    s = models.load_config(config)[which]
    assert counts.layer_matmul_params(s) == pin["layer_matmul_params"]
    assert counts.prompt_flops(s, 700) == pin["prompt_flops_700"]
    assert counts.head_flops(s) == pin["head_flops"]
    assert counts.token_flops(s, 900) == pin["token_flops_900"]
    for rows, want in pin["attention_call"].items():
        assert list(counts.attention_call(s, [json.loads(rows)])) == want
    rows = [json.loads(r) for r in pin["attention_call"]]
    assert list(counts.attention_call(s, rows)) == pin["attention_call_all"]
    assert counts.attention_calls(s, rows) == [
        (s["layers"], *pin["attention_call_all"])]


@pytest.mark.parametrize("config", CONFIGS)
def test_rehearsal_weights_and_gaps_are_the_pinned_ones(config):
    pin = PINS["configs"][config]
    conf = models.load_config(config)
    t, d = (models.rehearsal_spec(conf[w]) for w in WHICH)
    gen = system.weight_generator("random")
    params = dict(zip(WHICH, gen.generate(t, d, PINS["seed"])))
    for which in WHICH:
        got = {}
        for path, x in jax.tree_util.tree_flatten_with_path(params[which])[0]:
            a = np.asarray(x.astype(jnp.float32), np.float64)
            got[_leaf_name(path)] = [a.sum(), np.abs(a).sum()]
        want = pin[which]["rehearsal_checksums"]
        assert list(got) == list(want)
        for name, sums in want.items():
            assert got[name] == pytest.approx(sums, rel=1e-6, abs=1e-9), name
    g = pin["rehearsal_gaps"]
    gaps, control = reference.served_gaps(t, params["target"], g["prompt"],
                                          g["served"], quant_control=True)
    np.testing.assert_allclose(gaps, g["gaps"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(control, g["control_gaps"], rtol=1e-5,
                               atol=1e-6)


# -- a new layout enters as files ---------------------------------------------

# A toy layout: residual tanh MLP layers, the odd ones counted as attending
# a window of keys, every layer's weights in one stacked leaf.
TOY = '''
import jax
import jax.numpy as jnp

from harness.reference import mm, rms


def spec(name, c):
    return {"name": name, "layers": int(c["num_hidden_layers"]),
            "d_model": int(c["hidden_size"]),
            "width": int(c["intermediate_size"]),
            "vocab": int(c["vocab_size"]), "window": int(c["window"])}


def rehearsal(s):
    return dict(s, layers=1)


def program_fields(s):
    return {"family": "dense", "num_layers": s["layers"],
            "d_model": s["d_model"], "num_heads": 1, "num_kv_heads": 1,
            "d_ff": s["width"], "vocab_size": s["vocab"],
            "head_dim": s["d_model"]}


def param_shapes(s):
    L, d, f, V = s["layers"], s["d_model"], s["width"], s["vocab"]
    return {"embed": (V, d), "layers": {"up": (L, d, f), "down": (L, f, d)},
            "final_norm": {"scale": (d,)}, "lm_head": (V, d)}


def param_scale(path, s):
    if path.endswith("scale"):
        return 1.0, 0.0
    return 0.0, {"up": s["d_model"], "down": s["width"]}.get(
        path.rsplit("/", 1)[-1], 50) ** -0.5


def hidden(s, quant, params, tokens):
    h = params["embed"][tokens].astype(jnp.float32)

    def body(hh, lw):
        return hh + mm(jnp.tanh(mm(hh, lw["up"], quant)), lw["down"],
                       quant), None
    h, _ = jax.lax.scan(body, h, params["layers"])
    return rms(h, params["final_norm"]["scale"], 1e-6)


def logits(s, quant, params, rows):
    return mm(rows, params["lm_head"].T, quant)


def layer_matmul_params(s):
    return 2 * s["d_model"] * s["width"]


def head_flops(s):
    return 2.0 * s["d_model"] * s["vocab"]


def prompt_flops(s, prompt_len):
    return 2.0 * s["layers"] * layer_matmul_params(s) * prompt_len


def token_flops(s, keys):
    return 2.0 * s["layers"] * layer_matmul_params(s) + head_flops(s)


def attention_calls(s, rows):
    def call(limit):
        keys = sum(min(L, limit) for T, L in rows)
        return 4.0 * s["d_model"] * keys, 4.0 * s["d_model"] * keys
    windowed = s["layers"] // 2
    return [(s["layers"] - windowed, *call(1 << 60)),
            (windowed, *call(s["window"]))]
'''

TOY_MODEL = {"hidden_size": 64, "intermediate_size": 96, "vocab_size": 50,
             "window": 8, "arch_module": "toy_banded"}


def _toy_root(tmp_path: Path) -> Path:
    (tmp_path / "archs").mkdir()
    (tmp_path / "configs").mkdir()
    (tmp_path / "archs" / "toy_banded.py").write_text(TOY)
    raw = dict(TOY_MODEL, name="toy-1chip", num_hidden_layers=3,
               logit_gap_limit=0.125, registered_as=None,
               draft=dict(TOY_MODEL, name="toy-draft", num_hidden_layers=1))
    (tmp_path / "configs" / "toy-1chip.json").write_text(json.dumps(raw))
    return tmp_path


def _tree_bytes(root: Path) -> dict:
    return {p: p.read_bytes() for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_layout_enters_as_files_only(tmp_path):
    before = _tree_bytes(CHIP)
    conf = models.load_config("toy-1chip", root=_toy_root(tmp_path))
    t, d = conf["target"], conf["draft"]
    assert (t["arch"], t["layers"], d["layers"]) == ("toy_banded", 3, 1)
    assert models.rehearsal_spec(t)["layers"] == 1

    cfg = system.model_config(t)
    assert (cfg.name, cfg.num_layers, cfg.d_ff, cfg.vocab_size) == (
        "toy-1chip", 3, 96, 50)

    gen = system.weight_generator("random")
    tp, dp = gen.generate(t, d, 3000000019)
    assert tp["layers"]["up"].shape == (3, 64, 96)
    assert dp["layers"]["down"].shape == (1, 96, 64)
    assert tp["layers"]["up"].dtype == jnp.bfloat16
    assert bool(jnp.all(tp["final_norm"]["scale"] == 1))

    # the reference's gaps are the toy's equations, worked out here
    prompt, served = [3, 1, 4, 1, 5], [9, 2, 6]
    gaps, _ = reference.served_gaps(t, tp, prompt, served)
    w = jax.tree.map(lambda x: np.asarray(x.astype(jnp.float32), np.float64),
                     tp)
    h = w["embed"][np.array(prompt + served[:-1])]
    for up, down in zip(w["layers"]["up"], w["layers"]["down"]):
        h = h + np.tanh(h @ up) @ down
    h = h / np.sqrt(np.mean(h * h, -1, keepdims=True) + 1e-6)
    lg = h[len(prompt) - 1:] @ w["lm_head"].T
    want = (lg.max(-1) - lg[np.arange(3), served]) / lg.std(-1)
    np.testing.assert_allclose(gaps, want, rtol=1e-4, atol=1e-5)

    n = 2 * 64 * 96
    assert counts.layer_matmul_params(t) == n
    assert counts.prompt_flops(t, 7) == 2.0 * 3 * n * 7
    assert counts.token_flops(t, 100) == 2.0 * 3 * n + 2.0 * 64 * 50
    # two kinds of layer, each counted by its own keys
    groups = counts.attention_calls(t, [(1, 100)])
    assert groups == [(2, 4.0 * 64 * 100, 4.0 * 64 * 100),
                      (1, 4.0 * 64 * 8, 4.0 * 64 * 8)]
    with pytest.raises(ValueError, match="attention_calls"):
        counts.attention_call(t, [(1, 100)])

    # the readers go through the same counts
    peaks = peaks_for("TPU v5 lite")
    req = SimpleNamespace(admitted=[1.0], prompt_len=7, stamps=[(2.0, 2)])
    win = SimpleNamespace(open=0.0, close=10.0, requests={0: req},
                          backend_calls=[(3.0, "step",
                                          {"gamma": 0, "ctx": [99]})])
    ctx = readers.Context(win, t, d, peaks, 0.0, 0, None)
    assert readers.target_flops_in_window(ctx) == (
        counts.prompt_flops(t, 7) + counts.head_flops(t)
        + counts.token_flops(t, 8))
    assert readers.attention_roofline_seconds(ctx) == pytest.approx(sum(
        k * counts.roofline_seconds(f, b, peaks) for k, f, b in groups))
    assert _tree_bytes(CHIP) == before


def test_a_configuration_names_its_module(tmp_path):
    (tmp_path / "configs").mkdir()
    raw = json.loads((CHIP / "configs" / "paper-7b-1chip.json").read_text())
    del raw["arch_module"]
    (tmp_path / "configs" / "paper-7b-1chip.json").write_text(json.dumps(raw))
    with pytest.raises(ValueError, match="arch_module"):
        models.load_config("paper-7b-1chip", root=tmp_path)


# -- the dense module refuses other layouts -------------------------------------

@pytest.mark.parametrize("extra,named", [
    ({"num_experts": 128}, ["num_experts"]),
    ({"n_routed_experts": 64}, ["n_routed_experts"]),
    ({"moe_intermediate_size": 1024}, ["moe_intermediate_size"]),
    ({"sliding_window": 2048,
      "layer_types": ["sliding_attention", "full_attention"]},
     ["layer_types", "sliding_window"]),
    ({"use_sliding_window": True, "sliding_window": 4096},
     ["use_sliding_window"]),
    ({"kv_lora_rank": 512}, ["kv_lora_rank"]),
    ({"hidden_act": "gelu_pytorch_tanh"}, ["hidden_act"]),
])
def test_the_dense_module_refuses_what_it_does_not_compute(extra, named):
    raw = json.loads((CHIP / "configs" / "paper-7b-1chip.json").read_text())
    dense = models.load_arch("dense_decoder")
    with pytest.raises(ValueError) as e:
        dense.spec("paper-7b-1chip", dict(raw, **extra))
    for key in named:
        assert key in str(e.value)


def test_the_dense_module_takes_a_window_that_no_layer_uses():
    """Qwen2 files carry ``sliding_window`` with the window switched off."""
    raw = json.loads((CHIP / "configs" / "paper-7b-1chip.json").read_text())
    dense = models.load_arch("dense_decoder")
    want = dense.spec("paper-7b-1chip", raw)
    assert dense.spec("paper-7b-1chip", dict(
        raw, sliding_window=4096, use_sliding_window=False,
        layer_types=["full_attention"] * 14)) == want
