"""Plain reference of a dense pre-norm decoder: RMSNorm, rotary
attention with optional QKV bias and grouped KV heads, SwiGLU, untied
head, causal LM loss with z-loss, in ``jax.numpy`` at ``highest``
matmul precision. Nothing here imports the system under test.

Each module in ``refmodels/`` is one architecture's reference, named
as the configuration's ``family`` (the program's own architecture
field); ``refmodel.model_ref`` loads it by file path. Every module exports:

* ``leaf_specs(model) -> {name: (shape, init std)}``: the flat leaves,
  sorted by name (the position fixes each leaf's ``fold_in`` index in
  ``refmodel.init_weights``); std 0 means zeros. The names and shapes
  are the program's, which the harness checks before a run.
* ``loss_fn(params, tokens, model, dtype) -> scalar``: the mean training
  loss of one batch of token rows (labels = tokens), every term the
  program's loss has, computed in ``dtype``.
* ``step_flops(model, batch, seq) -> float``: forward + backward FLOPs
  of one local step, over the parameters each token uses.
* ``tiny(model) -> dict``: the configuration's keys to override so that
  a CPU test run holds the cell; only sizes change.

Departures: this is the program's dense decoder, so a published model
it stands for departs where the program does, and its configuration's
``assumed`` list says how (stablelm: RMSNorm for LayerNorm, rotary on
the whole head). Norms scale by ``1 + w``, ``w`` zero at init; the
z-loss term is the program's; there is no dropout.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def head_dim(model: dict) -> int:
    return int(model.get("head_dim") or model["d_model"] // model["num_heads"])


def leaf_specs(model: dict) -> dict[str, tuple[tuple[int, ...], float]]:
    """Flat leaf name -> (shape, init std); std 0 means zeros."""
    L, d, f, V = (model[k] for k in ("num_layers", "d_model", "d_ff", "vocab_size"))
    qf = model["num_heads"] * head_dim(model)
    kvf = model["num_kv_heads"] * head_dim(model)
    std, emb_std = float(model["init_std"]), float(model["embed_init_std"])
    out = {
        "blocks.attn.wq": ((L, d, qf), std),
        "blocks.attn.wk": ((L, d, kvf), std),
        "blocks.attn.wv": ((L, d, kvf), std),
        "blocks.attn.wo": ((L, qf, d), std),
        "blocks.attn_norm": ((L, d), 0.0),
        "blocks.mlp.w_gate": ((L, d, f), std),
        "blocks.mlp.w_up": ((L, d, f), std),
        "blocks.mlp.w_down": ((L, f, d), std),
        "blocks.mlp_norm": ((L, d), 0.0),
        "embed.embedding": ((V, d), emb_std),
        "embed.final_norm": ((d,), 0.0),
        "embed.lm_head": ((d, V), std),
    }
    if model.get("qkv_bias"):
        out["blocks.attn.bq"] = ((L, qf), 0.0)
        out["blocks.attn.bk"] = ((L, kvf), 0.0)
        out["blocks.attn.bv"] = ((L, kvf), 0.0)
    return dict(sorted(out.items()))


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (b, s, heads, hd); rotates the two halves of each head."""
    s, hd = x.shape[1], x.shape[3]
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    c = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sn = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def loss_fn(params: dict, tokens: jax.Array, model: dict, dtype) -> jax.Array:
    """Mean causal-LM loss (+ z-loss) of ``tokens`` (labels = tokens)."""
    p = {k: v.astype(dtype) for k, v in params.items()}
    eps = float(model["rms_norm_eps"])
    H, KV, hd = model["num_heads"], model["num_kv_heads"], head_dim(model)
    theta = float(model["rope_theta"])
    mm = functools.partial(jnp.einsum, precision=HIGHEST)
    blocks = {k[len("blocks."):]: v for k, v in p.items() if k.startswith("blocks.")}

    def layer(x, bp):
        b, s, _ = x.shape
        h = _rms(x, bp["attn_norm"][None, None], eps)
        q = mm("bsd,df->bsf", h, bp["attn.wq"])
        k = mm("bsd,df->bsf", h, bp["attn.wk"])
        v = mm("bsd,df->bsf", h, bp["attn.wv"])
        if "attn.bq" in bp:
            q, k, v = q + bp["attn.bq"], k + bp["attn.bk"], v + bp["attn.bv"]
        q = _rope(q.reshape(b, s, H, hd), theta)
        k = _rope(k.reshape(b, s, KV, hd), theta)
        v = v.reshape(b, s, KV, hd)
        if KV != H:
            k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
        scores = mm("bshe,bthe->bhst", q, k) / jnp.asarray(math.sqrt(hd), dtype)
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        scores = jnp.where(causal, scores, jnp.asarray(-1e30, dtype))
        probs = jax.nn.softmax(scores, axis=-1)
        o = mm("bhst,bthe->bshe", probs, v).reshape(b, s, H * hd)
        x = x + mm("bsf,fd->bsd", o, bp["attn.wo"])
        h = _rms(x, bp["mlp_norm"][None, None], eps)
        g = mm("bsd,df->bsf", h, bp["mlp.w_gate"])
        u = mm("bsd,df->bsf", h, bp["mlp.w_up"])
        return x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, bp["mlp.w_down"]), None

    x = p["embed.embedding"][tokens]
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, blocks)
    x = _rms(x, p["embed.final_norm"], eps)
    logits = mm("bsd,dv->bsv", x, p["embed.lm_head"])[:, :-1]
    labels = tokens[:, 1:]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold + jnp.asarray(float(model["z_loss"]), dtype) * jnp.square(lse)
    return jnp.mean(nll)


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matmul (all but the embedding
    table, the norms and the biases)."""
    L, d, f, V = (model[k] for k in ("num_layers", "d_model", "d_ff", "vocab_size"))
    qf = model["num_heads"] * head_dim(model)
    kvf = model["num_kv_heads"] * head_dim(model)
    return L * (d * qf + 2 * d * kvf + qf * d + 3 * d * f) + d * V


def step_flops(model: dict, batch: int, seq: int) -> float:
    """PaLM's count for a decoder's training step: ``6 * N`` per token
    for the N matmul parameters (forward, and twice that backward; the
    embedding lookup is not a matmul) plus ``12 * layers * seq *
    attention width`` per token for the score and value products.
    Recomputation (remat, flash attention's backward) is not counted."""
    attn_width = model["num_heads"] * head_dim(model)
    per_token = 6 * matmul_params(model) + 12 * model["num_layers"] * seq * attn_width
    return float(per_token) * batch * seq


def tiny(model: dict) -> dict:
    """Two layers, width 128, four heads, vocabulary 4096: every leaf of
    the configuration is still there, QKV bias included."""
    return {"num_layers": 2, "d_model": 128, "num_heads": 4, "num_kv_heads": 4,
            "d_ff": 256, "vocab_size": 4096}
