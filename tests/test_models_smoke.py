"""Per-architecture smoke tests (deliverable f): reduced config of the same
family, one forward/train step + one decode step on CPU, asserting output
shapes and absence of NaNs. Full configs are exercised only by the dry-run."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import (ARCH_IDS, SHAPES, get_config,
                           get_smoke_config, sized_config)
from repro.models import build_model


@pytest.fixture(scope="module")
def key():
    return jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=32):
    b = {
        "tokens": jnp.zeros((B, S), jnp.int32),
        "labels": jnp.ones((B, S), jnp.int32),
    }
    if cfg.n_prefix_embeddings:
        b["prefix_embeds"] = jnp.zeros(
            (B, cfg.n_prefix_embeddings, cfg.d_model), jnp.bfloat16
        )
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_loss(arch, key):
    cfg = get_smoke_config(arch)
    assert cfg.n_layers <= 2 and cfg.d_model <= 512
    if cfg.moe is not None:
        assert cfg.moe.n_experts <= 4
    model = build_model(cfg)
    params = model.init(key)
    batch = _batch(cfg)
    logits, aux = jax.jit(model.forward)(params, batch)
    assert logits.shape == (2, 32, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert bool(jnp.isfinite(loss))
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step_no_nans(arch, key):
    """One full gradient step with the paper's optimizer on the reduced
    arch; params stay finite and the loss is differentiable."""
    from repro.core import leaf_compressor_from_ratio, memsgd, constant_eta
    from repro.optim import apply_updates

    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(key)
    batch = _batch(cfg)
    tx = memsgd(leaf_compressor_from_ratio(0.05), constant_eta(0.05))
    s = tx.init(params)

    @jax.jit
    def step(params, s):
        grads, metrics = jax.grad(model.loss, has_aux=True)(params, batch)
        u, s = tx.update(grads, s)
        return apply_updates(params, u), s, metrics

    params, s, metrics = step(params, s)
    leaves = jax.tree.leaves(params)
    assert all(bool(jnp.all(jnp.isfinite(l.astype(jnp.float32)))) for l in leaves)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_decode_step(arch, key):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params = model.init(key)
    cache = model.init_cache(2, 64)
    logits, cache2 = jax.jit(model.decode_step)(
        params, cache, jnp.zeros((2,), jnp.int32)
    )
    assert logits.shape == (2, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    # cache index advanced
    idx = cache2["index"] if "index" in cache2 else None
    if idx is not None:
        assert int(idx) == 1


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The production configs carry the exact assigned hyperparameters."""
    cfg = get_config(arch)
    expect = {
        "rwkv6-3b": (32, 2560, 8960, 65536),
        "qwen1.5-4b": (40, 2560, 6912, 151936),
        "yi-9b": (48, 4096, 11008, 64000),
        "musicgen-medium": (48, 1536, 6144, 2048),
        "qwen3-moe-30b-a3b": (48, 2048, 768, 151936),
        "qwen3-4b": (36, 2560, 9728, 151936),
        "internvl2-26b": (48, 6144, 16384, 92553),
        "granite-3-8b": (40, 4096, 12800, 49155),
        "recurrentgemma-9b": (38, 4096, 12288, 256000),
        "granite-moe-3b-a800m": (32, 1536, 512, 49155),
    }[arch]
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == expect
    assert cfg.source
    if arch == "qwen3-moe-30b-a3b":
        assert cfg.moe.n_experts == 128 and cfg.moe.top_k == 8
    if arch == "granite-moe-3b-a800m":
        assert cfg.moe.n_experts == 40 and cfg.moe.top_k == 8
    if arch == "qwen1.5-4b":
        assert cfg.qkv_bias
    if arch == "qwen3-4b":
        assert cfg.qk_norm
    if arch == "recurrentgemma-9b":
        assert cfg.hybrid.pattern == ("rec", "rec", "attn")
        assert cfg.n_kv_heads == 1
    if arch == "yi-9b":
        assert cfg.n_kv_heads == 4


@pytest.mark.parametrize("size", ["smoke", "full"])
def test_sized_config_cuts_depth_only(size):
    """The train CLI's --size/--layers: depth is the only cut."""
    base = (get_config if size == "full" else get_smoke_config)("rwkv6-3b")
    cut = sized_config("rwkv6-3b", size, layers=1)
    assert cut.n_layers == 1
    assert cut.replace(n_layers=base.n_layers) == base
    assert sized_config("rwkv6-3b", size) == base
    with pytest.raises(ValueError, match="layers"):
        sized_config("rwkv6-3b", size, layers=0)


def test_shape_configs_match_assignment():
    s = SHAPES
    assert (s["train_4k"].seq_len, s["train_4k"].global_batch) == (4096, 256)
    assert (s["prefill_32k"].seq_len, s["prefill_32k"].global_batch) == (32768, 32)
    assert (s["decode_32k"].seq_len, s["decode_32k"].global_batch) == (32768, 128)
    assert (s["long_500k"].seq_len, s["long_500k"].global_batch) == (524288, 1)
