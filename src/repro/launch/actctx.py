"""Activation-sharding hints.

The model code is mesh-agnostic; the launch layer installs NamedShardings
here before tracing. ``shard_act`` constrains the residual stream at scan
boundaries (keeps remat-saved activations model-sharded); ``shard_as`` is the
generic hook used for the loss chunks ("loss_act": gather the bf16 hidden
once instead of psumming f32 logits — found via the dry-run, EXPERIMENTS
§Perf). "moe_ep_mesh" carries the mesh for the MoE layer's shard_map.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional

import jax

_SPECS: Dict[str, object] = {}


def set_sharding(name: str, sharding) -> None:
    if sharding is None:
        _SPECS.pop(name, None)
    else:
        _SPECS[name] = sharding


@contextlib.contextmanager
def sharding_hints(**kw):
    prev = dict(_SPECS)
    for k, v in kw.items():
        set_sharding(k, v)
    try:
        yield
    finally:
        _SPECS.clear()
        _SPECS.update(prev)


# back-compat alias used by launch/steps.py
@contextlib.contextmanager
def act_sharding(sharding, **kw):
    with sharding_hints(act=sharding, **kw):
        yield


def shard_as(x: jax.Array, name: str) -> jax.Array:
    s = _SPECS.get(name)
    if s is None:
        return x
    return jax.lax.with_sharding_constraint(x, s)


def shard_act(x: jax.Array) -> jax.Array:
    s = _SPECS.get("act")
    if s is None or x.ndim != 3:
        return x
    return jax.lax.with_sharding_constraint(x, s)
