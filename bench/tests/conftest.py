"""Toy sizes for the cells whose files ``perfbench_tiny`` does not cut.

``perfbench_tiny.make`` cuts the configurations by the program's own keys
(``d_model``, ``num_layers``, ...) and the mixes it names. The MLA + MoE
configuration keeps the catalog's keys (``hidden_size``,
``num_hidden_layers``, ...), and its mix, ``switch-groups-64``, has a
driver of its own. Both cuts are added here, before any test builds its
toy tree, so that every cell of BENCHMARK.json runs at toy size through
the same helper.
"""
from __future__ import annotations

import json

import perfbench_tiny as T

# the MLA + MoE block at toy widths: 1 dense and 2 moe layers, 2 of the
# router's 8 experts held from expert 2 on, top-3
CATALOG_TINY = dict(num_layers=3, num_hidden_layers=3, hidden_size=64,
                    num_attention_heads=4, num_key_value_heads=4,
                    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                    v_head_dim=16, intermediate_size=96,
                    moe_intermediate_size=32, router_experts=8,
                    n_routed_experts=2, first_held_expert=2,
                    num_experts_per_tok=3, n_shared_experts=1,
                    vocab_size=256)
T.MIXES.setdefault("switch-groups-64", dict(
    group=4, prefill_batch=2, adapters=3, buckets=[16, 32],
    prompt=dict(dist="lognormal", median=20, sigma=0.6, min=8, max=32),
    output=dict(dist="uniform", min=3, max=5), check_tokens=12))

_make = T.make


def _make_with_catalog_configs(root):
    sp = _make(root)
    for c in sp.doc["configs"]:
        cfg = json.loads((T.REPO / c["file"]).read_text())
        if "hidden_size" in cfg:
            (root / c["file"]).write_text(json.dumps(dict(cfg,
                                                          **CATALOG_TINY)))
    return sp


T.make = _make_with_catalog_configs
