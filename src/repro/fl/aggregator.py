"""Server-side aggregation — the streaming-first aggregation plane.

Aggregators implement one uniform **streaming protocol**, registry-keyed
like pipeline stages and runtime policies:

* ``begin(meta) -> weight`` — a client contribution starts; ``meta`` is
  the transmitted message-header dict (``num_samples``, ``client``,
  ``round`` ...). Returns the sample weight every subsequent
  ``accept_item`` call for this contribution should carry.
* ``accept_item(name, value, weight)`` — one payload item of one
  contribution, folded into the running aggregate immediately. Called
  straight from the wire decode loop (``ContainerReceiver.consume`` ->
  ``WireDecoder`` -> here), so a quantized+compressed item is
  dequantized, folded, and freed before the next item arrives — the
  server never materializes a client's payload dict.
* ``finish() -> dict`` — close the aggregate and reset.

``accept(message)`` is the batch shim: it drives the exact same protocol
methods in payload order, so batch and streaming aggregation run
*identical arithmetic in identical order* — bitwise-equal results by
construction (tests assert this across every transmission mode).

:class:`FedAvgAggregator` is the paper-faithful path: Task Results arrive
*already dequantized* (the pipeline's value stages decode in the
streaming loop), and aggregation is a sample-weighted average at original
precision — running sum + one in-flight item, never K full models.

:class:`QuantizedFedAvgAggregator` is the beyond-paper path: the server
keeps the uplink in wire form (``decode_values=False``) and folds each
int8 item through the buffer-donating dequant-accumulate-into kernel as
it arrives — one fp32 running sum per tensor, updated in place, no
per-client payload buffering and no fp32 temporary of the dequantized
contribution. The aggregate equals dequantize-then-average (tests
assert this).

:class:`LoRAFedAvgAggregator` is the parameter-efficient path: clients
ship :class:`~repro.peft.lowrank.LowRankDelta` factor pairs (via the
``lora`` stage or native adapters) and the server folds weighted factors
— the dense average materializes once, at ``finish()``, via one fused
low-rank merge per tensor.

Thread safety: ``begin``/``accept_item``/``finish`` serialize on a
per-instance lock, so many clients may stream into one aggregator
concurrently (the MemoryMeter acceptance test drives 32 senders at
once). Fold *order* under concurrency follows stream interleaving;
sample-weighted sums are order-independent in exact arithmetic, and the
deterministic runtimes (sequential controller, event scheduler) fold in
a fixed order anyway.
"""
from __future__ import annotations

import threading
from collections.abc import Callable, Mapping
from typing import Any, Union

import jax.numpy as jnp
import numpy as np

from repro.core.messages import Message
from repro.core.quantization import QuantizedTensor, dequantize, dequantize_batch
from repro.kernels import ops
from repro.obs import trace as obs_trace
from repro.peft.lowrank import LowRankDelta


class Aggregator:
    """Protocol base: the streaming begin/accept_item/finish surface.

    Subclasses override the three protocol methods; ``accept`` (the
    whole-message shim) is derived and should not normally be overridden.

    ``consumes_wire`` declares that the aggregator folds payload items in
    their *wire* form (QuantizedTensor / LowRankDelta) — the job system
    reads it (:func:`aggregator_consumes_wire`) and builds the uplink
    pipeline with ``decode_values=False`` so value stages skip their
    decode hooks and the raw containers reach ``accept_item``.
    """

    name: str = "aggregator"
    consumes_wire: bool = False

    def weight_of(self, meta: Mapping[str, Any]) -> float:
        """The item weight one contribution's headers imply (pure)."""
        return float(meta.get("num_samples", 1))

    def begin(self, meta: Mapping[str, Any]) -> float:
        """Register one client contribution; returns its item weight."""
        raise NotImplementedError

    def accept_item(self, name: str, value: Any, weight: float) -> None:
        """Fold one payload item of one contribution."""
        raise NotImplementedError

    def finish(self) -> dict[str, Any]:
        """Close the aggregate, reset state, return the result."""
        raise NotImplementedError

    def accept(self, result: Message) -> None:
        """Batch shim: drive the streaming protocol in payload order.

        The contribution is registered (``begin``) only after every item
        folded, so a payload that fails validation mid-message never
        leaves a phantom sample weight diluting ``finish()``.
        """
        w = self.weight_of(result.headers)
        for name, value in result.payload.items():
            self.accept_item(name, value, w)
        self.begin(result.headers)


class FedAvgAggregator(Aggregator):
    """Sample-weighted incremental FedAvg at original precision."""

    name = "fedavg"

    def __init__(self) -> None:
        self._sum: dict[str, np.ndarray] = {}
        self._scratch: dict[tuple[int, ...], np.ndarray] = {}
        self._weight = 0.0
        self.accepted = 0
        self._lock = threading.Lock()

    def begin(self, meta: Mapping[str, Any]) -> float:
        w = self.weight_of(meta)
        with obs_trace.span("agg.begin", "agg",
                            client=str(meta.get("client", "")), weight=w):
            with self._lock:
                self._weight += w
                self.accepted += 1
        return w

    def accept_item(self, name: str, value: Any, weight: float) -> None:
        """Streaming entry point: one item of one client's result.

        The fold reuses a per-shape scratch buffer for the weighted
        contribution (``w * x`` lands in scratch, scratch adds into the
        running sum), so folding an item allocates nothing after the
        first round — same arithmetic, same order, bitwise-equal
        results to the naive ``sum += value * weight``. Traced, a device
        array's copy to the host is ``host.d2h`` and the arithmetic
        ``host.fold``.
        """
        if isinstance(value, QuantizedTensor):
            raise TypeError(
                f"FedAvgAggregator received a quantized item {name!r}; "
                "decode values on the uplink pipeline (the default) or use "
                "QuantizedFedAvgAggregator"
            )
        arr = np.asarray(ops.to_host(value), dtype=np.float32)
        with self._lock, obs_trace.span("host.fold", "host", nbytes=arr.nbytes):
            acc = self._sum.get(name)
            if acc is None:
                # an array even at 0-d (a ufunc returns a NumPy scalar
                # there), so later folds and finish() work in place
                self._sum[name] = np.multiply(arr, np.float32(weight),
                                              out=np.empty(arr.shape, np.float32))
                return
            scratch = self._scratch.get(arr.shape)
            if scratch is None:
                scratch = np.empty(arr.shape, np.float32)
                self._scratch[arr.shape] = scratch
            np.multiply(arr, np.float32(weight), out=scratch)
            acc += scratch

    def finish(self) -> dict[str, np.ndarray]:
        with obs_trace.span("agg.finish", "agg"), self._lock:
            if self._weight <= 0:
                raise RuntimeError("no results accepted")
            # the running sums are this aggregator's own: normalise them
            # in place and hand them out, one pass and no second copy
            out, w = self._sum, np.float32(self._weight)
            with obs_trace.span("host.fold", "host",
                                nbytes=sum(a.nbytes for a in out.values())):
                for arr in out.values():
                    np.divide(arr, w, out=arr)
            self._sum = {}
            self._weight = 0.0
            self.accepted = 0
        return out


class QuantizedFedAvgAggregator(Aggregator):
    """Aggregates blockwise8 Task Results directly from int8 payloads —

    the server never materializes K fp32 models. ``accept_item`` is a
    **fused streaming fold**: each contribution runs the buffer-donating
    dequant-accumulate-into kernel
    (:func:`repro.kernels.ops.dequant_accumulate8_into`), updating one
    fp32 running sum per tensor in place the moment the item decodes.
    Server state is O(1 accumulator per tensor) regardless of how many
    clients stream in — no per-client payload buffering, and the
    dequantized contribution never exists as a standalone fp32
    temporary. Non-quantized (small) items fall back to plain averaging.
    """

    name = "quantized-fedavg"
    consumes_wire = True

    def __init__(self) -> None:
        self._acc: dict[str, Any] = {}                    # running weighted sums
        self._shape: dict[str, tuple[int, ...]] = {}      # orig shapes
        self._plain = FedAvgAggregator()
        self._plain_names: set[str] = set()
        self._weight = 0.0
        self.accepted = 0
        self._lock = threading.Lock()

    def begin(self, meta: Mapping[str, Any]) -> float:
        w = self.weight_of(meta)
        with obs_trace.span("agg.begin", "agg",
                            client=str(meta.get("client", "")), weight=w):
            with self._lock:
                self._weight += w
                self.accepted += 1
        return w

    def accept_item(self, name: str, value: Any, weight: float) -> None:
        if isinstance(value, QuantizedTensor):
            if value.fmt != "blockwise8":
                raise TypeError(
                    f"QuantizedFedAvgAggregator supports blockwise8; {name!r} is {value.fmt}"
                )
            with self._lock:
                known = self._shape.get(name)
                if known is not None and known != tuple(value.orig_shape):
                    raise ValueError(
                        f"contribution for {name!r} has shape "
                        f"{tuple(value.orig_shape)}; aggregate holds {known}"
                    )
                self._shape[name] = tuple(value.orig_shape)
                tr = obs_trace.ACTIVE
                if tr is None:
                    self._acc[name] = ops.dequant_accumulate8_into(
                        self._acc.get(name), value.payload, value.absmax, weight
                    )
                else:
                    with tr.span("kernel.dequant_accumulate8", "kernel",
                                 item=name,
                                 nbytes=int(np.asarray(value.payload).nbytes)):
                        self._acc[name] = ops.dequant_accumulate8_into(
                            self._acc.get(name), value.payload, value.absmax, weight
                        )
        else:
            self._plain.accept_item(name, value, weight)
            with self._lock:
                self._plain_names.add(name)

    def finish(self) -> dict[str, np.ndarray]:
        with obs_trace.span("agg.finish", "agg"), self._lock:
            out: dict[str, np.ndarray] = {}
            if self._acc:
                # one donated dispatch scales every accumulator where it
                # lives; each then comes down once, its crop a view (the
                # host copies are read-only, and nothing more is made)
                inv = np.float32(1.0) / np.float32(self._weight if self._weight else 1.0)
                names = list(self._acc)
                scaled = ops.scale_into([self._acc[n] for n in names], inv)
                # the fold's single sync point: every dispatch so far was
                # async; one barrier beats a round trip per tensor below
                ops.block_until_ready(scaled)
                for name, acc in zip(names, scaled):
                    shape = self._shape[name]
                    n = int(np.prod(shape))
                    out[name] = ops.to_host(acc).reshape(-1)[:n].reshape(shape)
            if self._plain_names:
                # reuse the plain aggregator's running sum (shares self._weight)
                self._plain._weight = self._weight
                out.update(self._plain.finish())
            self._acc = {}
            self._shape = {}
            self._plain_names = set()
            self._weight = 0.0
            self.accepted = 0
        return out


class LoRAFedAvgAggregator(Aggregator):
    """Streams :class:`~repro.peft.lowrank.LowRankDelta` contributions
    into a sample-weighted average **without ever materializing a dense
    per-client delta**. ``accept_item`` appends the factor pair per
    tensor — the left factor pre-scaled by ``weight * alpha/rank``, the
    right factor kept by reference — so server state during the fold is
    ``O(clients * rank * dim)``, independent of the dense model size
    (the MemoryMeter acceptance test pins this). The weighted average

    .. math:: (1/W) \\sum_i w_i (\\alpha_i/r_i) A_i B_i
              = \\text{concat}_1(\\tilde A_i) \\cdot \\text{concat}_0(B_i) / W

    materializes exactly once, in ``finish()``, as one fused
    block-matmul dispatch per tensor
    (:func:`repro.kernels.ops.low_rank_merge` over the concatenated
    factor blocks). Contributions may carry *different* ranks/alphas per
    client — the concatenation is rank-heterogeneous by construction.

    Non-low-rank items fall back: QuantizedTensor stragglers (a composed
    ``lora -> quantize`` uplink keeps small dense tensors quantized)
    dequantize and fold through the plain path; dense arrays fold
    directly. Wire-form uplinks (``consumes_wire``) mean the job system
    builds the task-result pipeline with ``decode_values=False``.
    """

    name = "lora-fedavg"
    consumes_wire = True

    def __init__(self) -> None:
        self._a: dict[str, list[np.ndarray]] = {}        # weight-scaled left factors
        self._b: dict[str, list[np.ndarray]] = {}        # right factors (by reference)
        self._shape: dict[str, tuple[int, ...]] = {}
        self._plain = FedAvgAggregator()
        self._plain_names: set[str] = set()
        self._weight = 0.0
        self.accepted = 0
        self._lock = threading.Lock()

    def begin(self, meta: Mapping[str, Any]) -> float:
        w = self.weight_of(meta)
        with obs_trace.span("agg.begin", "agg",
                            client=str(meta.get("client", "")), weight=w):
            with self._lock:
                self._weight += w
                self.accepted += 1
        return w

    def accept_item(self, name: str, value: Any, weight: float) -> None:
        if isinstance(value, LowRankDelta):
            with self._lock:
                known = self._shape.get(name)
                if known is not None and known != tuple(value.orig_shape):
                    raise ValueError(
                        f"contribution for {name!r} has shape "
                        f"{tuple(value.orig_shape)}; aggregate holds {known}"
                    )
                self._shape[name] = tuple(value.orig_shape)
                # the left factor absorbs this contribution's sample
                # weight and LoRA scale (one O(m*r) scaled copy); the
                # right factor is held as received — finish() then needs
                # no per-contribution bookkeeping at all
                self._a.setdefault(name, []).append(
                    np.asarray(value.a, np.float32)
                    * np.float32(weight * value.scale)
                )
                self._b.setdefault(name, []).append(
                    np.asarray(value.b, np.float32)
                )
        else:
            if isinstance(value, QuantizedTensor):
                # small tensors a composed lora->quantize stack left
                # quantized: recover precision, fold through plain FedAvg
                value = np.asarray(dequantize(value), np.float32)
            self._plain.accept_item(name, value, weight)
            with self._lock:
                self._plain_names.add(name)

    def finish(self) -> dict[str, np.ndarray]:
        with obs_trace.span("agg.finish", "agg"), self._lock:
            out: dict[str, np.ndarray] = {}
            inv = np.float32(1.0) / np.float32(self._weight if self._weight else 1.0)
            tr = obs_trace.ACTIVE
            for name, a_parts in self._a.items():
                shape = self._shape[name]
                a_cat = a_parts[0] if len(a_parts) == 1 else np.concatenate(a_parts, axis=1)
                b_parts = self._b[name]
                b_cat = b_parts[0] if len(b_parts) == 1 else np.concatenate(b_parts, axis=0)
                if tr is None:
                    dense = ops.low_rank_merge(a_cat, b_cat, inv)
                else:
                    with tr.span("kernel.lora_merge", "kernel", item=name,
                                 rank=int(a_cat.shape[1])):
                        dense = ops.low_rank_merge(a_cat, b_cat, inv)
                out[name] = np.asarray(dense).reshape(shape).astype(np.float32)
            if self._plain_names:
                # reuse the plain aggregator's running sum (shares self._weight)
                self._plain._weight = self._weight
                out.update(self._plain.finish())
            self._a = {}
            self._b = {}
            self._shape = {}
            self._plain_names = set()
            self._weight = 0.0
            self.accepted = 0
        return out


class CollectingSink:
    """Protocol-shaped sink that just rebuilds the payload dict — the
    fallback for consumers that still need whole-message results (e.g. a
    third-party policy without a streaming override)."""

    def __init__(self) -> None:
        self.payload: dict[str, Any] = {}
        self.meta: dict[str, Any] = {}

    def begin(self, meta: Mapping[str, Any]) -> float:
        self.meta = dict(meta)
        return float(meta.get("num_samples", 1))

    def accept_item(self, name: str, value: Any, weight: float) -> None:
        self.payload[name] = value

    def finish(self) -> dict[str, Any]:
        """Close collect mode: any QuantizedTensor items still in wire
        form (a ``decode_values=False`` uplink) dequantize in **one
        fused kernel dispatch per format group** with a single device
        sync (:func:`repro.core.quantization.dequantize_batch`) instead
        of a dispatch-and-sync per item in the receive loop — bitwise
        the same dense payload, batched decode schedule. The payload
        dict is updated in place and returned."""
        self.payload = dequantize_batch(self.payload)
        return self.payload


# ---------------------------------------------------------------------------
# Aggregator registry (the job system resolves "aggregator" names here)
# ---------------------------------------------------------------------------

_AGGREGATORS: dict[str, Callable[..., Aggregator]] = {}


def register_aggregator(
    name: str,
) -> Callable[[Callable[..., Aggregator]], Callable[..., Aggregator]]:
    """Decorator binding a spec name to an aggregator factory — the same
    registry pattern as ``repro.core.pipeline.register_stage`` and
    ``repro.runtime.async_agg.register_policy``; third-party aggregators
    become addressable from job specs without touching :mod:`repro.fl.job`.
    """

    def deco(factory: Callable[..., Aggregator]) -> Callable[..., Aggregator]:
        if name in _AGGREGATORS:
            raise ValueError(
                f"aggregator name {name!r} already registered ({_AGGREGATORS[name]})"
            )
        _AGGREGATORS[name] = factory
        return factory

    return deco


def registered_aggregators() -> tuple[str, ...]:
    return tuple(sorted(_AGGREGATORS))


def build_aggregator(spec: Union[str, Mapping[str, Any], Aggregator, None],
                     default: str = "fedavg") -> Aggregator:
    """``"fedavg"`` | ``{"aggregator": "quantized-fedavg"}`` | instance."""
    if spec is None:
        spec = default
    if isinstance(spec, Aggregator):
        return spec
    kwargs: dict[str, Any] = {}
    if isinstance(spec, Mapping):
        kwargs = dict(spec)
        try:
            spec = kwargs.pop("aggregator")
        except KeyError:
            raise ValueError(
                f'aggregator dict spec needs an "aggregator" name key '
                f"(got {sorted(kwargs)}); registered: {registered_aggregators()}"
            ) from None
    try:
        factory = _AGGREGATORS[spec]
    except KeyError:
        raise ValueError(
            f"unknown aggregator {spec!r}; registered: {registered_aggregators()}"
        ) from None
    return factory(**kwargs)


def aggregator_consumes_wire(
    spec: Union[str, Mapping[str, Any], Aggregator, None],
    default: str = "fedavg",
) -> bool:
    """Whether the aggregator a spec names folds wire-form payload items
    (``consumes_wire``) — resolved *without* instantiating, so the job
    system can decide ``decode_values`` while building pipelines. Unknown
    names resolve False here; :func:`build_aggregator` raises later with
    the full registered list."""
    if spec is None:
        spec = default
    if isinstance(spec, Aggregator):
        return bool(spec.consumes_wire)
    if isinstance(spec, Mapping):
        spec = spec.get("aggregator", default)
    factory = _AGGREGATORS.get(spec)
    return bool(getattr(factory, "consumes_wire", False))


register_aggregator("fedavg")(FedAvgAggregator)
register_aggregator("quantized-fedavg")(QuantizedFedAvgAggregator)
register_aggregator("lora-fedavg")(LoRAFedAvgAggregator)
