"""Operations and bytes of the work each cell does, and the chip's peaks.

These count the operation, not the implementation: a rewrite of a kernel
changes the time it takes and never the count it is judged against.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of one chip, keyed by ``jax.Device.device_kind``. A device
    that is not in the table is an error, not a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def bound_s(flops: float, nbytes: float, pk: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def sidedelta_call(tokens: int, n: int, m: int, k: int, adapters: int,
                   x_bytes: int = 2, val_bytes: int = 4):
    """One side-delta call over ``tokens`` adapted rows of an (n, m)
    matrix whose adapters hold ``k`` nonzeros each, ``adapters`` of them
    distinct in the call. 2 FLOPs per nonzero per adapted token; bytes are
    the activations read, the f32 delta written and each distinct
    adapter's values read once (indices are left out, so no layout can
    read fewer bytes than are counted)."""
    flops = 2.0 * k * tokens
    nbytes = tokens * (n * x_bytes + m * 4) + adapters * k * val_bytes
    return flops, nbytes


def switch_bytes(nonzeros: int, weight_bytes: int = 4,
                 val_bytes: int = 4) -> float:
    """Unload one pack and load the next, each of ``nonzeros`` entries:
    per entry and per pass, one read and one write of the weight element
    and one read of its value."""
    return 2.0 * nonzeros * (2 * weight_bytes + val_bytes)


def matmul_params(cfg: dict) -> int:
    """Weights one token multiplies by in one layer."""
    d, H, KV = cfg["d_model"], cfg["num_heads"], cfg["num_kv_heads"]
    hd, ff = cfg["head_dim"], cfg["d_ff"]
    ffn = 3 if cfg["act"] == "silu" else 2
    return d * H * hd + 2 * d * KV * hd + H * hd * d + ffn * d * ff


def token_flops(cfg: dict, context: int, logits: bool,
                adapter_nonzeros: int = 0) -> float:
    """Model FLOPs of one token at a context of ``context`` keys: 2 per
    matmul weight in every layer, attention's QK^T and PV at that context,
    the output head when the token's logits are computed, and 2 per
    adapter nonzero per layer when its adapter rides as a side delta
    (``adapter_nonzeros`` is one layer's count)."""
    L, H, hd = cfg["num_layers"], cfg["num_heads"], cfg["head_dim"]
    per_layer = (2.0 * matmul_params(cfg) + 4.0 * context * H * hd
                 + 2.0 * adapter_nonzeros)
    head = 2.0 * cfg["d_model"] * cfg["vocab_size"] if logits else 0.0
    return L * per_layer + head


def prefill_flops(cfg: dict, start: int, stop: int, logits: bool = True,
                  adapter_nonzeros: int = 0) -> float:
    """Tokens at positions [start, stop) of one sequence, each attending
    to itself and all before it; logits for the last one only."""
    n = stop - start
    keys = (stop * (stop + 1) - start * (start + 1)) // 2
    L, H, hd = cfg["num_layers"], cfg["num_heads"], cfg["head_dim"]
    per_token = 2.0 * matmul_params(cfg) + 2.0 * adapter_nonzeros
    head = 2.0 * cfg["d_model"] * cfg["vocab_size"] if logits else 0.0
    return L * (n * per_token + 4.0 * keys * H * hd) + head


def sum_bounds(calls: Iterable, pk: Dict[str, float]) -> float:
    """Summed roofline bound of (flops, bytes) calls."""
    return sum(bound_s(f, b, pk) for f, b in calls)
