"""Public jit'd quantization ops: arbitrary-shape arrays in, blocked

payloads out, with backend dispatch (Pallas on TPU, Pallas-interpret for
kernel validation, pure-jnp ref elsewhere — same semantics everywhere,
enforced by tests/test_kernels_*.py).

Dispatch discipline (the wire hot path): each public op is a **single**
jitted computation covering flatten + pad + quantize, so one tensor
costs one XLA dispatch instead of a chain of eager reshape/astype/pad
dispatches followed by the kernel. ``jax.jit``'s compilation cache is
keyed by (shape, dtype) — the shape-bucketed cache: the first tensor of
a given shape compiles, every later layer of the same shape reuses the
executable. All ops dispatch **asynchronously**; callers that encode a
whole message batch their dispatches and block once via
:func:`block_until_ready` (see ``repro.core.quantization.
quantize_batch``) instead of syncing per tensor inside the streamer
loop.

Traced runs (``repro.obs.trace.ACTIVE`` set) see every host<->device
boundary here, around the same calls an untraced run makes: the codec
and fold entry points open ``dev.dispatch`` (``kind``, ``elems``) and,
when NumPy arguments ride up with the dispatch, ``host.h2d`` inside it
(``nbytes``: the host's hold of that dispatch, the copy's synchronous
part); :func:`scale_into` opens ``dev.scale`` (``elems``);
:func:`block_until_ready` opens ``dev.sync``; :func:`to_host` is
``np.asarray`` with a device array's wait (``dev.sync``) and copy
(``host.d2h``, ``nbytes``) timed apart.
"""
from __future__ import annotations

import contextlib
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.obs import trace as obs_trace

# jitted ref-backend entry points (the ref functions build 15-compare /
# 16-select networks — uncompiled tracing per call would dominate on CPU)
_REF_Q8 = jax.jit(ref.quantize_blockwise8)
_REF_D8 = jax.jit(ref.dequantize_blockwise8)
_REF_Q4 = {
    fmt: jax.jit(functools.partial(ref.quantize_4bit, code=code))
    for fmt, code in (("fp4", ref.FP4_CODE), ("nf4", ref.NF4_CODE))
}
_REF_D4 = {
    fmt: jax.jit(functools.partial(ref.dequantize_4bit, code=code))
    for fmt, code in (("fp4", ref.FP4_CODE), ("nf4", ref.NF4_CODE))
}


def block_until_ready(values) -> None:
    """Barrier for a batch of async-dispatched op results (pytree of
    arrays; non-JAX leaves pass through untouched)."""
    with obs_trace.span("dev.sync", "dev"):
        jax.block_until_ready(values)


def to_host(x) -> np.ndarray:
    """``np.asarray(x)``. Traced, a device array is first waited for
    under ``dev.sync`` (``np.asarray`` waits at the same point), so that
    ``host.d2h`` times the copy alone; anything else is on the host
    already and converts untimed."""
    tr = obs_trace.ACTIVE
    if tr is None or not isinstance(x, jax.Array):
        return np.asarray(x)
    with tr.span("dev.sync", "dev"):
        x.block_until_ready()
    with tr.span("host.d2h", "host", nbytes=int(x.nbytes)):
        return np.asarray(x)


_NOOP = contextlib.nullcontext()


@contextlib.contextmanager
def _traced_dispatch(tr, kind: str, elems: int, args: tuple):
    up = sum(a.nbytes for a in args if isinstance(a, np.ndarray))
    with tr.span("dev.dispatch", "dev", kind=kind, elems=elems):
        if not up:
            yield
            return
        with tr.span("host.h2d", "host", nbytes=int(up)):
            yield


def _dispatch_span(kind: str, elems, *args):
    """``dev.dispatch`` around one entry point's dispatch (and
    ``host.h2d`` for the NumPy ``args`` it carries up); a shared no-op
    when tracing is off."""
    tr = obs_trace.ACTIVE
    if tr is None:
        return _NOOP
    return _traced_dispatch(tr, kind, int(elems), args)


def _flat_blocks(x: jnp.ndarray, block: int) -> jnp.ndarray:
    """Flatten + fp32 cast + zero-pad to whole quant blocks (traced inside
    each op's jit, so the chain is one fused executable per input shape).

    Wire-format padding is one block max (<=16 KiB for int8, <=256 B for
    4-bit); the Pallas paths pad *rows* to their grid granularity
    (:func:`_pad_rows`) and slice the result back, so grid alignment
    never inflates the transmitted message."""
    flat = jnp.asarray(x).reshape(-1).astype(jnp.float32)
    n = flat.shape[0]
    padded = int(np.ceil(n / block)) * block
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(padded // block, block)


# whole-op jitted entry points (ref backend): flatten/pad/quantize fused
_REF_Q8_FULL = jax.jit(lambda x: ref.quantize_blockwise8(_flat_blocks(x, ref.BLOCK8)))
_REF_Q4_FULL = {
    fmt: jax.jit(
        functools.partial(
            lambda x, code: ref.quantize_4bit(_flat_blocks(x, ref.BLOCK4), code),
            code=code,
        )
    )
    for fmt, code in (("fp4", ref.FP4_CODE), ("nf4", ref.NF4_CODE))
}


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _ref_d8_full(q, absmax, shape, dtype):
    out = ref.dequantize_blockwise8(q, absmax)
    n = int(np.prod(shape)) if shape else 1
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)


@functools.partial(jax.jit, static_argnames=("fmt", "shape", "dtype"))
def _ref_d4_full(packed, absmax, fmt, shape, dtype):
    code = ref.FP4_CODE if fmt == "fp4" else ref.NF4_CODE
    out = ref.dequantize_4bit(packed, absmax, code)
    n = int(np.prod(shape)) if shape else 1
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)
from repro.kernels.quant_blockwise8 import (
    BLOCK8,
    ROWS,
    dequantize_blockwise8_pallas,
    quantize_blockwise8_pallas,
)
from repro.kernels.quant_nf4 import (
    BLOCK4,
    ROWS4,
    dequantize_4bit_pallas,
    quantize_4bit_pallas,
)
from repro.kernels.fused_dequant_agg import (
    dequant_accumulate8_into_pallas,
    dequant_accumulate8_pallas,
)

#: valid backend selections (public: job specs validate against this)
BACKENDS = ("auto", "ref", "pallas", "pallas_interpret")
_BACKENDS = BACKENDS
_backend = os.environ.get("REPRO_KERNEL_BACKEND", "auto")


def set_backend(name: str) -> None:
    global _backend
    if name not in _BACKENDS:
        raise ValueError(f"backend must be one of {_BACKENDS}, got {name!r}")
    _backend = name


def get_backend() -> str:
    if _backend != "auto":
        return _backend
    # Pallas compiled path on TPU; ref (identical semantics) on CPU hosts.
    return "pallas" if jax.default_backend() == "tpu" else "ref"


@contextlib.contextmanager
def backend(name: str):
    """Scoped backend override: ``with ops.backend("pallas_interpret"):``.

    Restores the previous selection on exit, so tests and benchmarks can
    compare backends without mutating (and forgetting to restore) the
    module global."""
    global _backend
    prev = _backend
    set_backend(name)
    try:
        yield
    finally:
        _backend = prev


def _pad_rows(x2d: jnp.ndarray, row_multiple: int) -> tuple[jnp.ndarray, int]:
    nblocks = x2d.shape[0]
    padded = int(np.ceil(nblocks / row_multiple)) * row_multiple
    if padded != nblocks:
        x2d = jnp.pad(x2d, ((0, padded - nblocks), (0, 0)))
    return x2d, nblocks


# ---------------------------------------------------------------------------
# blockwise int8
# ---------------------------------------------------------------------------

def quantize_blockwise8(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Any-shape float array -> ((nblocks, 4096) int8, (nblocks,) absmax).

    One async jitted dispatch on every backend (flatten/pad/quantize
    fused; shape-bucketed by jit's compilation cache)."""
    with _dispatch_span("q8", x.size, x):
        backend = get_backend()
        if backend == "ref":
            return _REF_Q8_FULL(x)
        return _pallas_q8_full(x, interpret=(backend == "pallas_interpret"))


def dequantize_blockwise8(
    q: jnp.ndarray, absmax: jnp.ndarray, shape, dtype=jnp.float32
) -> jnp.ndarray:
    with _dispatch_span("d8", np.prod(shape), q, absmax):
        backend = get_backend()
        if backend == "ref":
            return _ref_d8_full(q, absmax, tuple(shape), np.dtype(dtype))
        return _pallas_d8_full(q, absmax, tuple(shape), np.dtype(dtype),
                               interpret=(backend == "pallas_interpret"))


# whole-op jitted entry points (Pallas backends): flatten, block and
# row padding, the kernel and the slice back to the wire layout are one
# executable, so no padded intermediate is materialized between eager
# dispatches — at full model width each one would cost a model-sized
# device buffer

@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_q8_full(x, interpret):
    x2d = _flat_blocks(x, BLOCK8)
    nblocks = x2d.shape[0]
    x2d, _ = _pad_rows(x2d, ROWS)
    q, am = quantize_blockwise8_pallas(x2d, interpret=interpret)
    return q[:nblocks], am[:nblocks]


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "interpret"))
def _pallas_d8_full(q, absmax, shape, dtype, interpret):
    nblocks = q.shape[0]
    q, _ = _pad_rows(q, ROWS)
    absmax = jnp.pad(absmax, (0, q.shape[0] - nblocks))
    out = dequantize_blockwise8_pallas(q, absmax, interpret=interpret)[:nblocks]
    n = int(np.prod(shape))
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# 4-bit (fp4 / nf4)
# ---------------------------------------------------------------------------

def quantize_4bit(x: jnp.ndarray, fmt: str) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Any-shape float array -> ((nblocks, 32) packed uint8, (nblocks,) absmax).

    One async jitted dispatch on every backend, like
    :func:`quantize_blockwise8`."""
    with _dispatch_span("q4", x.size, x):
        backend = get_backend()
        if backend == "ref":
            return _REF_Q4_FULL[fmt](x)
        return _pallas_q4_full(x, fmt=fmt, interpret=(backend == "pallas_interpret"))


def dequantize_4bit(
    packed: jnp.ndarray, absmax: jnp.ndarray, fmt: str, shape, dtype=jnp.float32
) -> jnp.ndarray:
    with _dispatch_span("d4", np.prod(shape), packed, absmax):
        backend = get_backend()
        if backend == "ref":
            return _ref_d4_full(packed, absmax, fmt, tuple(shape), np.dtype(dtype))
        return _pallas_d4_full(packed, absmax, fmt, tuple(shape), np.dtype(dtype),
                               interpret=(backend == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("fmt", "interpret"))
def _pallas_q4_full(x, fmt, interpret):
    x2d = _flat_blocks(x, BLOCK4)
    nblocks = x2d.shape[0]
    x2d, _ = _pad_rows(x2d, ROWS4)
    p, am = quantize_4bit_pallas(x2d, fmt=fmt, interpret=interpret)
    return p[:nblocks], am[:nblocks]


@functools.partial(jax.jit, static_argnames=("fmt", "shape", "dtype", "interpret"))
def _pallas_d4_full(packed, absmax, fmt, shape, dtype, interpret):
    nblocks = packed.shape[0]
    packed, _ = _pad_rows(packed, ROWS4)
    absmax = jnp.pad(absmax, (0, packed.shape[0] - nblocks))
    out = dequantize_4bit_pallas(packed, absmax, fmt=fmt, interpret=interpret)[:nblocks]
    n = int(np.prod(shape))
    return out.reshape(-1)[:n].reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# fused server-side aggregation
# ---------------------------------------------------------------------------

def dequant_accumulate8(
    qs: jnp.ndarray, absmaxes: jnp.ndarray, weights: jnp.ndarray
) -> jnp.ndarray:
    backend = get_backend()
    if backend == "ref":
        # On CPU the K-way einsum materializes a (K, nblocks, 4096) fp32
        # cast and benches *slower* than unfused (BENCH_5 speedup=0.22);
        # K donated in-place folds beat it and hold one fp32 buffer.
        qs = jnp.asarray(qs)
        absmaxes = jnp.asarray(absmaxes)
        weights = jnp.asarray(weights, jnp.float32)
        acc = jnp.zeros(qs.shape[1:], jnp.float32)
        for k in range(qs.shape[0]):
            acc = _REF_FOLD8(acc, qs[k], absmaxes[k], weights[k])
        return acc
    nblocks = qs.shape[1]
    padded = int(np.ceil(nblocks / ROWS)) * ROWS
    if padded != nblocks:
        qs = jnp.pad(qs, ((0, 0), (0, padded - nblocks), (0, 0)))
        absmaxes = jnp.pad(absmaxes, ((0, 0), (0, padded - nblocks)))
    out = dequant_accumulate8_pallas(
        qs, absmaxes, weights, interpret=(backend == "pallas_interpret")
    )
    return out[:nblocks]


# streaming fold: acc <- acc + w * dequant(q), accumulator donated so the
# fold never allocates (or leaves behind) an fp32 temporary per item
_REF_FOLD8 = jax.jit(
    lambda acc, q, absmax, w: acc
    + q.astype(jnp.float32) * ((absmax.astype(jnp.float32) / 127.0) * w)[:, None],
    donate_argnums=(0,),
)


def dequant_accumulate8_into(
    acc: jnp.ndarray | None, q: jnp.ndarray, absmax: jnp.ndarray, weight: float
) -> jnp.ndarray:
    """Fold one blockwise8 contribution into the running fp32 aggregate.

    ``acc`` is **donated**: the returned array reuses (aliases) its
    buffer, so a streaming aggregator's per-item fold is in-place — the
    dequantized contribution never materializes as a standalone fp32
    tensor. Pass ``acc=None`` to open the aggregate (returns
    ``weight * dequant(q)`` in a fresh buffer). ``q``: (nblocks, 4096)
    int8; ``absmax``: (nblocks,). The Pallas path may row-pad the
    accumulator; callers slice their flat view to the original element
    count (exactly like the other blocked ops).
    """
    with _dispatch_span("fold8", q.shape[0] * q.shape[1], q, absmax):
        backend = get_backend()
        if backend == "ref":
            if acc is None:
                acc = jnp.zeros(q.shape, jnp.float32)
            return _REF_FOLD8(acc, jnp.asarray(q), jnp.asarray(absmax),
                              jnp.float32(weight))
        nblocks = q.shape[0]
        q, _ = _pad_rows(q, ROWS)
        absmax = jnp.pad(absmax, (0, q.shape[0] - nblocks))
        if acc is None:
            acc = jnp.zeros(q.shape, jnp.float32)
        assert acc.shape == q.shape, (acc.shape, q.shape)
        return dequant_accumulate8_into_pallas(
            acc, q, absmax, jnp.float32(weight),
            interpret=(backend == "pallas_interpret"),
        )


# the aggregate's normalisation: every accumulator times one scalar, as
# one program over the tuple, the tuple donated so the scale is in place
_SCALE = jax.jit(lambda accs, s: tuple(a * s for a in accs), donate_argnums=(0,))


def scale_into(accs, s: np.float32) -> tuple[jnp.ndarray, ...]:
    """``tuple(a * s for a in accs)`` in one dispatch, on the device.

    ``accs`` is **donated**: each result reuses its accumulator's buffer,
    so normalising a round's running sums allocates no device memory and
    leaves the inputs deleted. ``s`` is traced (a float32 scalar), so no
    weight recompiles the program; one program per tuple of shapes.
    Traced, the dispatch is ``dev.scale`` (``elems``): not a codec
    ``dev.dispatch``, whose kinds are the codec and fold entry points."""
    accs = tuple(accs)
    with obs_trace.span("dev.scale", "dev", elems=int(sum(a.size for a in accs))):
        return _SCALE(accs, np.float32(s))


# ---------------------------------------------------------------------------
# low-rank (LoRA) factorization
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("rank",))
def _ref_lowrank_decompose(x: jnp.ndarray, rank: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused truncated SVD: cast + decompose + truncate + canonicalize in
    one executable per input shape. The SVD's per-component sign is
    mathematically arbitrary; flipping each right-factor row so its
    largest-|x| entry is positive pins one canonical factorization, so
    the same tensor always decomposes to the same wire bytes."""
    u, s, vt = jnp.linalg.svd(x.astype(jnp.float32), full_matrices=False)
    u, s, vt = u[:, :rank], s[:rank], vt[:rank, :]
    j = jnp.argmax(jnp.abs(vt), axis=1)
    signs = jnp.sign(vt[jnp.arange(rank), j])
    signs = jnp.where(signs == 0, jnp.float32(1.0), signs)
    a = u * (s * signs)[None, :]
    b = vt * signs[:, None]
    return a.astype(jnp.float32), b.astype(jnp.float32)


_REF_LOWRANK_MERGE = jax.jit(
    lambda a, b, scale: (a.astype(jnp.float32) @ b.astype(jnp.float32)) * scale
)


def low_rank_decompose(x: jnp.ndarray, rank: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(m, n)`` float array -> deterministic rank-``rank`` factors
    ``a (m, rank)``, ``b (rank, n)`` with ``a @ b`` the best (Eckart–
    Young) rank-``rank`` approximation of ``x``. Singular values are
    absorbed into ``a``; the factor signs are canonicalized so repeated
    calls on the same input are bitwise-identical (the wire's
    re-encode-equality contract).

    Backend note: every backend currently shares the fused ref jit —
    XLA has no Pallas-level SVD, so this entry point exists as the
    dispatch seam for a future randomized-subspace kernel, exactly like
    the quantize ops' ``backend == "ref"`` branches.
    """
    if rank < 1:
        raise ValueError(f"low-rank decompose needs rank >= 1, got {rank}")
    x = jnp.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"low_rank_decompose takes a 2-D array, got shape {x.shape}")
    if rank > min(x.shape):
        raise ValueError(f"rank {rank} exceeds min dim of shape {x.shape}")
    return _ref_lowrank_decompose(x, int(rank))


def low_rank_merge(a: jnp.ndarray, b: jnp.ndarray, scale: float) -> jnp.ndarray:
    """Merge a factor pair: ``scale * (a @ b)`` as one jitted fp32
    matmul dispatch (shape-bucketed like every other op here). Also the
    server-side fused aggregation primitive: concatenated factor blocks
    from K clients merge in one dispatch per tensor."""
    return _REF_LOWRANK_MERGE(jnp.asarray(a), jnp.asarray(b), jnp.float32(scale))
