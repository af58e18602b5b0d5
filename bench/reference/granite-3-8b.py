"""Plain float32 reference of the granite-3-8b configuration.

A pre-norm decoder as the Granite-3.0 config.json describes it: RMS
norm, grouped-query causal attention with rotary positions (rotate-half
form, theta from the file), SwiGLU feed-forward, a final RMS norm and
an output head tied to the embedding. The scalar multipliers are left
out, as the configuration file states.

It imports nothing of the program. Weights are drawn from the seed with
the draw order the configuration's initialiser uses (each matrix
normal / sqrt(fan-in), embedding normal * 0.02).

``rnd`` rounds every matmul operand: the identity for the reference, a
cast through a lower precision for the control.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _dense(key, d_in, d_out):
    return jax.random.normal(key, (d_in, d_out)) * (1.0 / math.sqrt(d_in))


def init(m: dict, key) -> dict:
    D, F, hd = m["d_model"], m["d_ff"], m["head_dim"]
    H, KV, V = m["n_heads"], m["n_kv_heads"], m["vocab_padded"]
    keys = jax.random.split(key, m["n_layers"] + 3)
    blocks = []
    for i in range(m["n_layers"]):
        ka, kf, _ = jax.random.split(keys[i], 3)
        a = jax.random.split(ka, 6)
        f = jax.random.split(kf, 3)
        blocks.append({
            "ln1": jnp.ones((D,), jnp.float32),
            "ln2": jnp.ones((D,), jnp.float32),
            "attn": {
                "wq": _dense(a[0], D, H * hd),
                "wk": _dense(a[1], D, KV * hd),
                "wv": _dense(a[2], D, KV * hd),
                "wo": _dense(a[3], H * hd, D),
            },
            "mlp": {
                "w_gate": _dense(f[0], D, F),
                "w_up": _dense(f[1], D, F),
                "w_down": _dense(f[2], F, D),
            },
        })
    params = {
        "embed": jax.random.normal(keys[-3], (V, D)) * 0.02,
        "blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *blocks),
        "ln_f": jnp.ones((D,), jnp.float32),
    }
    if not m["tie_embeddings"]:
        params["lm_head"] = _dense(keys[-2], D, V)
    return params


def _mm(a, b, rnd):
    return jnp.matmul(rnd(a), rnd(b), precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x: (B, S, heads, hd); rotate-half rotary embedding."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, m, x, rnd):
    B, S, _ = x.shape
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    q = _rope(_mm(x, p["wq"], rnd).reshape(B, S, H, hd), m["rope_theta"])
    k = _rope(_mm(x, p["wk"], rnd).reshape(B, S, KV, hd), m["rope_theta"])
    v = _mm(x, p["wv"], rnd).reshape(B, S, KV, hd)
    # query head h reads key/value head h // (H // KV)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    scores = jnp.einsum("bshd,bthd->bhst", rnd(q), rnd(k),
                        precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", rnd(probs), rnd(v), precision=HIGHEST)
    return _mm(out.reshape(B, S, H * hd), p["wo"], rnd)


def _swiglu(p, x, rnd):
    return _mm(jax.nn.silu(_mm(x, p["w_gate"], rnd)) * _mm(x, p["w_up"], rnd),
               p["w_down"], rnd)


def loss(params, tokens, labels, m: dict, rnd=lambda a: a):
    """Mean next-token cross-entropy over the real vocabulary."""
    params = jax.tree.map(rnd, params)
    eps = m["norm_eps"]
    x = params["embed"][tokens]
    for i in range(m["n_layers"]):
        blk = jax.tree.map(lambda a: a[i], params["blocks"])
        h = x + _attention(blk["attn"], m, _rmsnorm(x, blk["ln1"], eps), rnd)
        x = h + _swiglu(blk["mlp"], _rmsnorm(h, blk["ln2"], eps), rnd)
    head = params["embed"].T if m["tie_embeddings"] else params["lm_head"]
    logits = _mm(_rmsnorm(x, params["ln_f"], eps), head, rnd)[..., :m["vocab"]]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def matmul_params(m: dict) -> int:
    """Parameters that enter a matmul once per token (embedding gather left out;
    the tied head counts once, as the output matmul)."""
    D, F, hd = m["d_model"], m["d_ff"], m["head_dim"]
    attn = D * m["n_heads"] * hd * 2 + D * m["n_kv_heads"] * hd * 2
    return m["n_layers"] * (attn + 3 * D * F) + D * m["vocab_padded"]


def flops_per_token(m: dict, seq_len: int) -> float:
    """Model FLOPs of forward and backward per token, from the layer equations.

    6 per matmul parameter, plus attention's scores and weighted sum:
    2 * 2 * seq_len * head_dim per head forward, times 3 with the
    backward (the causal half is not subtracted, as is usual for MFU).
    No recomputation counted.
    """
    attn = 3 * 2 * 2 * seq_len * m["head_dim"] * m["n_heads"] * m["n_layers"]
    return 6.0 * matmul_params(m) + attn
