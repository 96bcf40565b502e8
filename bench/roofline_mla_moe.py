"""Operations and bytes of the MLA + MoE block's work (DeepSeek-V2).

As ``roofline.py`` does for the dense decoder, these count the operation,
not the implementation. Routed experts are counted from the program's
routing counter: 2 x 3 x d x expert width FLOPs per token-choice routed to
a held expert, whatever the router chose.
"""
from __future__ import annotations


def _attn_params(cfg: dict) -> int:
    """Weights one token multiplies by in one MLA layer, absorbed: q, the
    latent and rope key, W_uk into the query, W_uv out of the latent, and
    the output projection."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, r, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["kv_lora_rank"], cfg["v_head_dim"])
    return (d * H * (nope + rope) + d * (r + rope) + H * nope * r
            + H * r * v + H * v * d)


def _attn_context(cfg: dict) -> int:
    """Multiply-adds per key per token of absorbed attention: the latent
    and rope scores, and the latent values, for every head."""
    H, rope, r = (cfg["num_attention_heads"], cfg["qk_rope_head_dim"],
                  cfg["kv_lora_rank"])
    return H * (2 * r + rope)


def _layers(cfg: dict):
    dense = cfg["first_k_dense_replace"]
    return dense, cfg["num_hidden_layers"] - dense


def _ffn_params(cfg: dict):
    """(dense layer FFN, MoE layer's router plus shared experts)."""
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (3 * d * cfg["intermediate_size"],
            d * cfg["router_experts"] + 3 * d * cfg["n_shared_experts"] * ff)


def token_flops(cfg: dict, context: int, logits: bool) -> float:
    """FLOPs of one token at a context of ``context`` keys, routed experts
    left out (``routed_flops``): 2 per matmul weight, absorbed attention at
    that context, the output head when its logits are computed."""
    dense, moe = _layers(cfg)
    f_dense, f_moe = _ffn_params(cfg)
    per = 2.0 * _attn_params(cfg) + 2.0 * context * _attn_context(cfg)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] if logits else 0.0
    return (dense + moe) * per + 2.0 * (dense * f_dense + moe * f_moe) + head


def prefill_flops(cfg: dict, start: int, stop: int,
                  logits: bool = True) -> float:
    """Tokens at [start, stop) of one sequence, each attending to itself
    and all before it; logits for the last one only."""
    n = stop - start
    keys = (stop * (stop + 1) - start * (start + 1)) // 2
    dense, moe = _layers(cfg)
    f_dense, f_moe = _ffn_params(cfg)
    per = n * 2.0 * _attn_params(cfg) + 2.0 * keys * _attn_context(cfg)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] if logits else 0.0
    return ((dense + moe) * per
            + 2.0 * n * (dense * f_dense + moe * f_moe) + head)


def routed_flops(cfg: dict, choices: float) -> float:
    """Token-choices routed to held experts, each a gated SiLU."""
    return 2.0 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] \
        * choices


def moe_gmm_calls(cfg: dict, choices: float, experts_hit: float,
                  weight_bytes: int = 4, act_bytes: int = 2):
    """The grouped expert matmul of a set of calls, summed: FLOPs of the
    routed choices; bytes of each held expert's three matrices read once
    per call in which it has a choice (``experts_hit`` summed over calls),
    plus each choice's activations in and out."""
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    nbytes = (experts_hit * 3 * d * ff * weight_bytes
              + choices * 2 * d * act_bytes)
    return routed_flops(cfg, choices), nbytes
