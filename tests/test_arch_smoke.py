"""Per-architecture smoke tests (deliverable f): every assigned arch at a
reduced config runs one forward + one train step on CPU with correct output
shapes and no NaNs."""
import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.models import registry
from repro.training.optimizer import adamw_init, adamw_update, cosine_schedule

ARCHS = list(configs.ASSIGNED_ARCHS)


def _batch(cfg, rng, B=2, S=32):
    batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "encdec":
        batch["enc_emb"] = jax.random.normal(rng, (B, S, cfg.d_model),
                                             jnp.bfloat16)
    if cfg.family == "vlm":
        batch["image_emb"] = jax.random.normal(
            rng, (B, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    batch["labels"] = batch["tokens"]
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = configs.reduced(configs.get_config(arch))
    api = registry.get_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = api.init(rng)
    B, S = 2, 32
    batch = _batch(cfg, rng, B, S)
    h = api.forward(params, batch)
    S_total = S + (cfg.num_image_tokens if cfg.family == "vlm" else 0)
    assert h.shape == (B, S_total, cfg.d_model)
    assert bool(jnp.isfinite(h.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_decreases_nothing_nan(arch):
    cfg = configs.reduced(configs.get_config(arch))
    api = registry.get_model(cfg)
    rng = jax.random.PRNGKey(1)
    params = api.init(rng)
    opt = adamw_init(params)
    lr_fn = cosine_schedule(1e-3, 2, 100)
    batch = _batch(cfg, rng)

    def loss_fn(p):
        l, _ = api.loss(p, batch)
        return l

    loss0, grads = jax.value_and_grad(loss_fn)(params)
    assert bool(jnp.isfinite(loss0))
    params2, opt, gnorm = adamw_update(grads, opt, params, lr_fn=lr_fn)
    assert bool(jnp.isfinite(gnorm))
    loss1 = loss_fn(params2)
    assert bool(jnp.isfinite(loss1))
    # one step on the same batch should not increase the loss materially
    assert float(loss1) < float(loss0) + 0.1


@pytest.mark.parametrize("arch", ARCHS)
def test_draft_config_same_vocab(arch):
    cfg = configs.get_config(arch)
    dcfg = configs.get_draft_config(arch)
    assert dcfg.vocab_size == cfg.vocab_size
    assert registry.param_count(configs.reduced(dcfg)) > 0


def test_assigned_configs_exact():
    """The full configs must match the assignment table exactly."""
    c = configs.get_config("qwen2-72b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size) == (80, 8192, 64, 8, 29568, 152064)
    c = configs.get_config("gemma-7b")
    assert (c.num_layers, c.d_model, c.resolved_head_dim, c.d_ff,
            c.vocab_size) == (28, 3072, 256, 24576, 256000)
    c = configs.get_config("grok-1-314b")
    assert (c.moe_num_experts, c.moe_top_k, c.num_layers) == (8, 2, 64)
    c = configs.get_config("granite-moe-1b-a400m")
    assert (c.moe_num_experts, c.moe_top_k, c.d_ff) == (32, 8, 512)
    c = configs.get_config("mamba2-780m")
    assert (c.num_layers, c.d_model, c.ssm_state) == (48, 1536, 128)
    c = configs.get_config("zamba2-1.2b")
    assert (c.num_layers, c.d_model, c.ssm_state) == (38, 2048, 64)
    c = configs.get_config("whisper-medium")
    assert (c.enc_layers, c.dec_layers, c.d_model, c.vocab_size) == \
        (24, 24, 1024, 51865)
    c = configs.get_config("paligemma-3b")
    assert (c.num_layers, c.num_kv_heads, c.vocab_size) == (18, 1, 257216)
    c = configs.get_config("deepseek-7b")
    assert (c.num_layers, c.d_model, c.d_ff, c.vocab_size) == \
        (30, 4096, 11008, 102400)
    c = configs.get_config("qwen3-14b")
    assert (c.num_layers, c.d_model, c.qk_norm, c.vocab_size) == \
        (40, 5120, True, 151936)


def test_shapes_assignment():
    from repro.configs.base import shapes_for
    total = 0
    for arch in ARCHS:
        cfg = configs.get_config(arch)
        names = [s.name for s in shapes_for(cfg)]
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(names)
        if cfg.is_subquadratic:
            assert "long_500k" in names
        total += 4  # each arch is assigned 4 cells (skips documented)
    assert total == 40


def test_paper_7b_one_chip_cut_keeps_published_widths():
    """The one-chip cut of the paper pair changes the target's depth and
    its name, nothing else; the draft is the published draft."""
    full, cut = configs.get_config("paper-7b"), configs.get_config(
        "paper-7b-1chip")
    assert cut.num_layers == 14 and full.num_layers == 28
    assert cut.name != full.name       # the cost model caches by name
    assert cut == full.replace(name=cut.name, num_layers=14)
    assert (configs.get_draft_config("paper-7b-1chip")
            == configs.get_draft_config("paper-7b"))
    assert "paper-7b-1chip" in configs.list_archs()
    assert "paper-7b-1chip" not in configs.ASSIGNED_ARCHS
