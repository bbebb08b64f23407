"""Shared neural building blocks: parameter specs, norms, RoPE, GQA

attention (train / prefill / decode, full- and sliding-window), and gated
MLPs. Everything is functional (params are plain dicts) and every
parameter's logical sharding axes come from the same spec that built it —
a single source of truth consumed by ``repro.launch.sharding``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import base as B


# ---------------------------------------------------------------------------
# parameter specs: one definition -> params + logical axes
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    axes: tuple[Optional[str], ...]
    init: str = "normal"      # normal | zeros | ones
    scale: float = 0.02

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def build_params(rng: jax.Array, spec: dict[str, Any], dtype) -> dict[str, Any]:
    flat: dict[str, ParamDef] = {}
    _collect_defs(flat, spec, "")
    keys = jax.random.split(rng, max(len(flat), 1))
    arrays: dict[str, jnp.ndarray] = {}
    for (path, pd), key in zip(sorted(flat.items()), keys):
        if pd.init == "zeros":
            arr = jnp.zeros(pd.shape, dtype)
        elif pd.init == "ones":
            arr = jnp.ones(pd.shape, dtype)
        else:
            arr = (jax.random.normal(key, pd.shape, jnp.float32) * pd.scale).astype(dtype)
        arrays[path] = arr
    return _rebuild(arrays, spec, "")


# module-level recursions, not closures that call themselves: such a
# closure is a reference cycle that keeps what it captures (here, every
# parameter array on the device) alive until the cyclic collector runs

def _collect_defs(flat: dict[str, ParamDef], node: Any, path: str) -> None:
    if isinstance(node, ParamDef):
        flat[path] = node
    else:
        for k, v in node.items():
            _collect_defs(flat, v, f"{path}/{k}" if path else k)


def _rebuild(arrays: dict[str, jnp.ndarray], node: Any, path: str) -> Any:
    if isinstance(node, ParamDef):
        return arrays[path]
    return {k: _rebuild(arrays, v, f"{path}/{k}" if path else k) for k, v in node.items()}


def build_axes(spec: dict[str, Any]) -> dict[str, Any]:
    if isinstance(spec, ParamDef):
        return spec.axes
    return {k: build_axes(v) for k, v in spec.items()}


def stacked(pd: ParamDef, num: int) -> ParamDef:
    """Prepend a scanned-layer dim."""
    return ParamDef((num,) + pd.shape, (B.LAYER,) + pd.axes, pd.init, pd.scale)


def stack_spec(spec: dict[str, Any], num: int) -> dict[str, Any]:
    if isinstance(spec, ParamDef):
        return stacked(spec, num)
    return {k: stack_spec(v, num) for k, v in spec.items()}


# ---------------------------------------------------------------------------
# activation-sharding context (set by the launcher; no-op in smoke tests)
# ---------------------------------------------------------------------------

_SHARD_CTX: Optional[tuple[Any, dict[str, tuple[str, ...]]]] = None


def set_sharding_context(mesh, rules) -> None:
    """Install (mesh, logical->mesh rules) so model code can constrain

    activations. Called by launch.dryrun/train around lowering; smoke
    tests leave it unset and every constraint is a no-op."""
    global _SHARD_CTX
    _SHARD_CTX = None if mesh is None else (mesh, rules)


def _mesh_axis_size(axis: str) -> int:
    if _SHARD_CTX is None:
        return 1
    mesh, rules = _SHARD_CTX
    size = 1
    for m in rules.get(axis, ()):
        if m in mesh.axis_names:
            size *= mesh.shape[m]
    return size


def constrain(x: jnp.ndarray, axes: tuple[Optional[str], ...]) -> jnp.ndarray:
    """with_sharding_constraint by logical axes (divisibility-safe).

    REPRO_DISABLE_ACT_CONSTRAINTS=1 disables all activation constraints —
    used to re-measure pre-optimization baselines (§Perf)."""
    import os as _os

    if _SHARD_CTX is None or _os.environ.get("REPRO_DISABLE_ACT_CONSTRAINTS"):
        return x
    from jax.sharding import NamedSharding

    from repro.launch.sharding import spec_for

    mesh, rules = _SHARD_CTX
    spec = spec_for(x.shape, axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_heads_qkv(q, k, v, cfg) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pick the attention parallelism by divisibility (perf iteration 1,

    EXPERIMENTS.md §Perf): shard heads over `model` when the head count
    divides; otherwise fall back to **context parallelism** — q sharded
    over seq, k/v replicated over `model` — which keeps 40-head archs
    (qwen2.5-32b, llama4-scout) from GSPMD's replicate-and-repartition
    path. Decode (s == 1) uses heads-or-nothing.
    """
    model_sz = _mesh_axis_size(B.Q_FEAT)
    s = q.shape[1]
    if model_sz <= 1:
        return q, k, v
    if cfg.num_heads % model_sz == 0 and cfg.num_kv_heads % model_sz == 0:
        q = constrain(q, (B.BATCH, None, B.Q_FEAT, None))
        k = constrain(k, (B.BATCH, None, B.KV_FEAT, None))
        v = constrain(v, (B.BATCH, None, B.KV_FEAT, None))
    elif s > 1 and s % model_sz == 0:
        q = constrain(q, (B.BATCH, B.Q_FEAT, None, None))  # seq-sharded
        k = constrain(k, (B.BATCH, None, None, None))
        v = constrain(v, (B.BATCH, None, None, None))
    else:
        q = constrain(q, (B.BATCH, None, None, None))
        k = constrain(k, (B.BATCH, None, None, None))
        v = constrain(v, (B.BATCH, None, None, None))
    return q, k, v


# ---------------------------------------------------------------------------
# norms / activations
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(
    x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray, eps: float = 1e-5
) -> jnp.ndarray:
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(dtype)


def norm_spec(d: int) -> ParamDef:
    return ParamDef((d,), (B.EMBED,), init="zeros")


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope_table(
    positions: jnp.ndarray, head_dim: int, theta: float
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """positions: (...,) int -> cos/sin of shape positions.shape + (head_dim//2,)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray) -> jnp.ndarray:
    """x: (..., S, n, head_dim); cos/sin: (..., S, half) broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]  # add head axis
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def attention_spec(cfg: B.ModelConfig) -> dict[str, Any]:
    d, qf, kvf = cfg.d_model, cfg.q_feat, cfg.kv_feat
    spec: dict[str, Any] = {
        "wq": ParamDef((d, qf), (B.EMBED, B.Q_FEAT)),
        "wk": ParamDef((d, kvf), (B.EMBED, B.KV_FEAT)),
        "wv": ParamDef((d, kvf), (B.EMBED, B.KV_FEAT)),
        "wo": ParamDef((qf, d), (B.Q_FEAT, B.EMBED)),
    }
    if cfg.qkv_bias:
        spec["bq"] = ParamDef((qf,), (B.Q_FEAT,), init="zeros")
        spec["bk"] = ParamDef((kvf,), (B.KV_FEAT,), init="zeros")
        spec["bv"] = ParamDef((kvf,), (B.KV_FEAT,), init="zeros")
    return spec


def _project_qkv(x, p, cfg: B.ModelConfig, positions):
    bsz, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,df->bsf", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,df->bsf", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,df->bsf", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(bsz, s, cfg.num_heads, hd)
    k = k.reshape(bsz, s, cfg.num_kv_heads, hd)
    v = v.reshape(bsz, s, cfg.num_kv_heads, hd)
    if cfg.use_rope:
        cos, sin = rope_table(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return constrain_heads_qkv(q, k, v, cfg)


def sinusoidal_positions(s: int, d: int, dtype) -> jnp.ndarray:
    """Classic transformer sinusoidal table (whisper-style encoders)."""
    pos = np.arange(s)[:, None]
    dim = np.arange(d // 2)[None, :]
    ang = pos / np.power(10000.0, 2 * dim / d)
    table = np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)
    return jnp.asarray(table, dtype)


def _sdpa(q, k, v, mask, cfg: B.ModelConfig):
    """q: (b,s,H,hd); k,v: (b,t,KV,hd); mask: (b,1,1,s,t) or broadcastable."""
    bsz, s, H, hd = q.shape
    t = k.shape[1]
    KV = cfg.num_kv_heads
    G = H // KV
    qg = q.reshape(bsz, s, KV, G, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
    scores = scores / np.sqrt(hd)
    scores = jnp.where(mask, scores, jnp.float32(-1e30))
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(bsz, s, H * hd)


def sdpa_or_flash(q, k, v, cfg: B.ModelConfig, *, causal: bool, window: Optional[int]):
    """Full-sequence attention; routes to the flash Pallas kernel on the
    ``pallas`` backend (O(S) HBM traffic — §Perf pair 1 iteration 2) for
    every shape — lengths that do not tile its blocks are padded and
    masked inside the kernel wrapper, never sent to the jnp path —
    and to masked jnp softmax on the other backends.
    Shapes: q (b,s,H,hd); k,v (b,t,KV,hd)."""
    from repro.kernels import ops as kops

    bsz, s, H, hd = q.shape
    t = k.shape[1]
    if kops.get_backend() == "pallas":
        from repro.kernels.flash_attention import flash_attention_pallas

        out = flash_attention_pallas(
            q.transpose(0, 2, 1, 3),
            k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3),
            causal=causal,
            window=window,
        )
        return out.transpose(0, 2, 1, 3).reshape(bsz, s, H * hd)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool) if not causal else (j <= i)
    if window is not None:
        mask = mask & (i - j < window)
    return _sdpa(q, k, v, mask[None, None, None], cfg)


def attn_forward(
    x: jnp.ndarray,
    p: dict[str, jnp.ndarray],
    cfg: B.ModelConfig,
    *,
    causal: bool = True,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Training / prefill attention over a full sequence."""
    bsz, s, _ = x.shape
    positions = jnp.arange(s)[None, :]
    q, k, v = _project_qkv(x, p, cfg, positions)
    out = sdpa_or_flash(q, k, v, cfg, causal=causal, window=window)
    return jnp.einsum("bsf,fd->bsd", out, p["wo"].astype(x.dtype))


# -- decode caches -----------------------------------------------------------

def init_full_cache(cfg: B.ModelConfig, batch: int, max_len: int, dtype) -> dict[str, jnp.ndarray]:
    kvf = cfg.kv_feat
    return {
        "k": jnp.zeros((batch, max_len, kvf), dtype),
        "v": jnp.zeros((batch, max_len, kvf), dtype),
    }


def init_window_cache(cfg: B.ModelConfig, batch: int, window: int, dtype) -> dict[str, jnp.ndarray]:
    kvf = cfg.kv_feat
    return {
        "k": jnp.zeros((batch, window, kvf), dtype),
        "v": jnp.zeros((batch, window, kvf), dtype),
        "pos": jnp.full((batch, window), -1, jnp.int32),  # absolute positions stored
    }


def attn_decode(
    x: jnp.ndarray,
    p: dict[str, jnp.ndarray],
    cache: dict[str, jnp.ndarray],
    pos: jnp.ndarray,
    cfg: B.ModelConfig,
    *,
    window: Optional[int] = None,
) -> tuple[jnp.ndarray, dict[str, jnp.ndarray]]:
    """One-token decode step. x: (b, 1, d); pos: scalar int32 (current index).

    Full cache: writes k/v at ``pos`` and attends over [0, pos].
    Window cache: writes at ``pos % window`` (rolling) and attends over the
    stored absolute positions — O(window) memory for any context length.
    """
    bsz, one, _ = x.shape
    assert one == 1
    hd = cfg.resolved_head_dim
    positions = jnp.full((bsz, 1), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(x, p, cfg, positions)
    kvf = cfg.kv_feat
    k_flat = k_new.reshape(bsz, 1, kvf)
    v_flat = v_new.reshape(bsz, 1, kvf)
    if window is None:
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k_flat.astype(cache["k"].dtype), (0, pos, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v_flat.astype(cache["v"].dtype), (0, pos, 0))
        t = k_cache.shape[1]
        mask = (jnp.arange(t) <= pos)[None, None, None, None, :]  # (1,1,1,1,t)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        slot = pos % window
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k_flat.astype(cache["k"].dtype), (0, slot, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v_flat.astype(cache["v"].dtype), (0, slot, 0))
        pos_cache = jax.lax.dynamic_update_slice(
            cache["pos"], jnp.full((bsz, 1), pos, jnp.int32), (0, slot)
        )
        valid = (pos_cache >= 0) & (pos_cache <= pos) & (pos - pos_cache < window)
        mask = valid[:, None, None, None, :]  # (b,1,1,1,w)
        new_cache = {"k": k_cache, "v": v_cache, "pos": pos_cache}
    t = new_cache["k"].shape[1]
    k_all = new_cache["k"].reshape(bsz, t, cfg.num_kv_heads, hd).astype(x.dtype)
    v_all = new_cache["v"].reshape(bsz, t, cfg.num_kv_heads, hd).astype(x.dtype)
    out = _sdpa(q, k_all, v_all, mask, cfg)
    return jnp.einsum("bsf,fd->bsd", out, p["wo"].astype(x.dtype)), new_cache


# -- cross attention (enc-dec) ------------------------------------------------

def cross_attn_forward(
    x: jnp.ndarray,
    memory: jnp.ndarray,
    p: dict[str, jnp.ndarray],
    cfg: B.ModelConfig,
    kv: Optional[tuple[jnp.ndarray, jnp.ndarray]] = None,
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray]]:
    """Decoder cross-attention. q from ``x`` (b,s,d); k/v from ``memory``

    (b,t,d) — or from precomputed ``kv`` (decode path). No mask, no rope.
    Returns (out, (k, v)) so prefill can cache the projected memory.
    """
    bsz, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,df->bsf", x, p["wq"].astype(x.dtype)).reshape(bsz, s, cfg.num_heads, hd)
    if kv is None:
        t = memory.shape[1]
        k = jnp.einsum("btd,df->btf", memory, p["wk"].astype(x.dtype)).reshape(
            bsz, t, cfg.num_kv_heads, hd
        )
        v = jnp.einsum("btd,df->btf", memory, p["wv"].astype(x.dtype)).reshape(
            bsz, t, cfg.num_kv_heads, hd
        )
    else:
        k, v = kv
    mask = jnp.ones((1, 1, 1, 1, 1), bool)
    out = _sdpa(q, k.astype(x.dtype), v.astype(x.dtype), mask, cfg)
    return jnp.einsum("bsf,fd->bsd", out, p["wo"].astype(x.dtype)), (k, v)


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU)
# ---------------------------------------------------------------------------

def mlp_spec(cfg: B.ModelConfig) -> dict[str, Any]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "w_gate": ParamDef((d, f), (B.EMBED, B.MLP)),
        "w_up": ParamDef((d, f), (B.EMBED, B.MLP)),
        "w_down": ParamDef((f, d), (B.MLP, B.EMBED)),
    }


def mlp_forward(x: jnp.ndarray, p: dict[str, jnp.ndarray]) -> jnp.ndarray:
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u, p["w_down"].astype(x.dtype))


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------

def embed_spec(cfg: B.ModelConfig) -> dict[str, Any]:
    return {
        "embedding": ParamDef((cfg.vocab_size, cfg.d_model), (B.VOCAB, B.EMBED), scale=1.0),
        "lm_head": ParamDef((cfg.d_model, cfg.vocab_size), (B.EMBED, B.VOCAB)),
        "final_norm": norm_spec(cfg.d_model),
    }


def embed_tokens(tokens: jnp.ndarray, p: dict[str, jnp.ndarray], dtype) -> jnp.ndarray:
    return p["embedding"].astype(dtype)[tokens]


def lm_logits(x: jnp.ndarray, p: dict[str, jnp.ndarray]) -> jnp.ndarray:
    x = rms_norm(x, p["final_norm"])
    return jnp.einsum("bsd,dv->bsv", x, p["lm_head"].astype(x.dtype))


# ---------------------------------------------------------------------------
# LoRA adapters (parameter-efficient payloads)
# ---------------------------------------------------------------------------

def _lora_eligible(pd: ParamDef, rank: int) -> bool:
    if len(pd.shape) != 2:
        return False
    m, n = pd.shape
    return rank <= min(m, n) and rank * (m + n) < m * n


def lora_adapter_spec(spec: dict[str, Any], rank: int) -> dict[str, Any]:
    """The adapter ParamDef tree for a base parameter spec: every
    eligible 2-D matrix (rank fits, factors beat the dense form) maps to
    an ``{"a", "b"}`` factor pair carrying the base spec's sharding axes
    on its outer dims. ``b`` is zero-initialized, so a freshly built
    adapter contributes an exactly-zero delta — standard LoRA init.
    Norms, biases, and stacked (3-D) tensors are left out: those ship
    dense (the ``lora`` wire stage skips them for the same reason)."""
    out: dict[str, Any] = {}
    for k, v in spec.items():
        if isinstance(v, ParamDef):
            if _lora_eligible(v, rank):
                m, n = v.shape
                out[k] = {
                    "a": ParamDef((m, rank), (v.axes[0], None)),
                    "b": ParamDef((rank, n), (None, v.axes[1]), init="zeros"),
                }
        else:
            sub = lora_adapter_spec(v, rank)
            if sub:
                out[k] = sub
    return out


def lora_adapter_params(
    rng: jax.Array, spec: dict[str, Any], rank: int,
    dtype=jnp.float32, alpha: Optional[float] = None,
) -> dict[str, Any]:
    """Native-adapter mode: trainable LoRA pairs as a **flat** dict of
    :class:`~repro.peft.lowrank.LowRankDelta`, keyed by the base
    parameter's flat path. Clients training adapters put these straight
    into the Task Result payload — the ``lowrank`` wire kind, byte
    stages, and :class:`~repro.fl.aggregator.LoRAFedAvgAggregator`
    handle them identically to stage-decomposed deltas, and the uplink
    carries ``rank * (m + n)`` floats per matrix instead of ``m * n``."""
    from repro.peft.lowrank import LowRankDelta

    adapter_spec = lora_adapter_spec(spec, rank)
    arrays = build_params(rng, adapter_spec, dtype)
    alpha_f = float(alpha) if alpha is not None else float(rank)
    out: dict[str, Any] = {}

    def walk(base_node: dict[str, Any], pair_node: dict[str, Any], path: str) -> None:
        for k, pair in pair_node.items():
            p = f"{path}/{k}" if path else k
            base = base_node[k]
            if isinstance(base, ParamDef):
                a = np.asarray(pair["a"])
                out[p] = LowRankDelta(
                    a, np.asarray(pair["b"]), alpha_f, rank,
                    tuple(base.shape), a.dtype,
                )
            else:
                walk(base, pair, p)

    walk(spec, arrays, "")
    return out


def merge_lora(params: dict[str, Any], adapters: dict[str, Any]) -> dict[str, Any]:
    """Fold adapter deltas into a flat base state dict:
    ``params[name] + (alpha/rank) * a @ b`` per adapter entry, other
    entries untouched. The result dtype follows the base parameters."""
    out = dict(params)
    for name, delta in adapters.items():
        base = out[name]
        out[name] = (base + delta.to_dense().astype(base.dtype)).astype(base.dtype)
    return out


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def causal_lm_loss(logits: jnp.ndarray, labels: jnp.ndarray, z_loss: float = 0.0) -> jnp.ndarray:
    """Cross-entropy with optional z-loss; labels < 0 are masked."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
