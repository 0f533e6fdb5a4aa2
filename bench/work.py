"""Operations and bytes that each call of the token path needs, counted from
the model's true shapes.

The counts are what the mathematics asks for, not what an implementation
happens to do: a matmul is ``2·M·K·N`` operations on the true ``M`` (prompt
tokens or live slots), the weights are read once at their bit width,
attention is causal at the true context and the true head size.  Padding,
masks and lookup chains are not counted, so any implementation reads the
same work and no share of a roofline can exceed 1.

``roofline_s`` of one call is the least time the chip could take for it:
the larger of its operations over the int8 peak and its bytes over the HBM
bandwidth.  Shares sum that bound per call and divide by measured time.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, Iterable

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"

#: The fused projections of one block.
PROJECTIONS = ("qkv", "o", "up", "down")


class UnknownDevice(LookupError):
    """The device kind has no row in ``peaks.json``."""


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> Dict[str, float]:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in {path.name} "
            f"(known: {sorted(table)})"
        )
    return table[device_kind]


@dataclasses.dataclass
class Work:
    """Operations, bytes and the summed per-call roofline time of some calls."""

    ops: float = 0.0
    bytes: float = 0.0
    roofline_s: float = 0.0
    calls: int = 0

    def add(self, ops: float, nbytes: float, peaks: Dict[str, float], count: int = 1) -> None:
        """Add ``count`` calls of ``ops`` operations and ``nbytes`` bytes each."""
        self.ops += count * ops
        self.bytes += count * nbytes
        bound = max(ops / peaks["int8_ops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
        self.roofline_s += count * bound
        self.calls += count


@dataclasses.dataclass(frozen=True)
class BlockShapes:
    """True widths of the codified block (from a configuration file)."""

    d_model: int
    n_heads: int
    d_ff: int
    vocab: int
    n_layers: int
    bits: Dict[str, int]

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads

    def projection(self, name: str):
        """(K, N, weight bits) of one projection."""
        d, f = self.d_model, self.d_ff
        k, n = {"qkv": (d, 3 * d), "o": (d, d), "up": (d, f), "down": (f, d)}[name]
        return k, n, self.bits[name]


def qmatmul(m: int, k: int, n: int, bits: int):
    """(ops, bytes) of one fused matmul: int8 activations (m, k), weights
    (k, n) at ``bits``, an int32 bias of n, int8 outputs (m, n)."""
    ops = 2.0 * m * k * n
    nbytes = m * k + k * n * bits / 8.0 + 4.0 * n + m * n
    return ops, nbytes


def attention_prefill(plen: int, dh: int):
    """(ops, bytes) of one head's causal attention over a prompt of ``plen``:
    QK^T and PV over the ``plen·(plen+1)/2`` causal pairs; Q, K, V and the
    output each read or written once."""
    pairs = plen * (plen + 1) / 2.0
    return 4.0 * dh * pairs, 4.0 * plen * dh


def attention_decode(contexts: Iterable[int], dh: int):
    """(ops, bytes) of one head's decode attention for live slots whose
    context lengths (positions + 1) are ``contexts``."""
    ctx = list(contexts)
    total = float(sum(ctx))
    return 4.0 * dh * total, 2.0 * dh * total + 2.0 * dh * len(ctx)


def lm_head_ops(rows: int, shapes: BlockShapes) -> float:
    """Operations of the LM head on ``rows`` positions."""
    return 2.0 * rows * shapes.d_model * shapes.vocab


class TokenPathWork:
    """Accumulates the work of prefill and decode calls, per kernel family,
    and the operations the model needs for its tokens (for ``mfu``)."""

    def __init__(self, shapes: BlockShapes, peaks: Dict[str, float]) -> None:
        self.shapes = shapes
        self.peaks = peaks
        self.kernels: Dict[str, Work] = {"qmatmul": Work(), "qattention": Work()}
        self.needed_ops = 0.0

    def _block(self, m: int, attn) -> float:
        """Adds every layer's fused matmuls at ``m`` rows and one attention
        call ``attn`` = (ops, bytes) per head; returns the operations."""
        s = self.shapes
        ops_total = 0.0
        for name in PROJECTIONS:
            k, n, bits = s.projection(name)
            ops, nbytes = qmatmul(m, k, n, bits)
            self.kernels["qmatmul"].add(ops, nbytes, self.peaks, count=s.n_layers)
            ops_total += s.n_layers * ops
        calls = s.n_layers * s.n_heads
        self.kernels["qattention"].add(attn[0], attn[1], self.peaks, count=calls)
        return ops_total + calls * attn[0]

    def prefill(self, plen: int) -> None:
        """One prompt of ``plen`` tokens: every layer over every prompt
        token, and the LM head at the last prompt position."""
        ops = self._block(plen, attention_prefill(plen, self.shapes.d_head))
        self.needed_ops += ops + lm_head_ops(1, self.shapes)

    def decode(self, positions: Iterable[int]) -> None:
        """One decode step over the live slots at ``positions`` (the index of
        the token each slot writes; its context is ``position + 1``)."""
        ctx = [int(p) + 1 for p in positions]
        if not ctx:
            return
        ops = self._block(len(ctx), attention_decode(ctx, self.shapes.d_head))
        self.needed_ops += ops + lm_head_ops(len(ctx), self.shapes)
