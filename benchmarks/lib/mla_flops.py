"""Operations a latent-attention decoder with routed experts needs, from
its configuration's keys: the MATHEMATICS, whatever implements it (a
prefill that pads its heads to the flash kernel's one width, or a decode
that multiplies every cell of the slab and masks, does more and is
credited no more).

Per layer and attended (query, cell) pair, all heads: prefill attends per
head, 2 (nope + rope) in the scores and 2 value in the sum a head (40,960
at 64 heads of 128 + 64 / 128); decode attends absorbed, 2 (latent + rope)
and 2 latent a head (139,264 at a latent of 512). A causal prefill of n
tokens attends n (n + 1) / 2 pairs a layer.
"""

from __future__ import annotations


def prefill_attention_flops(pairs: int, cfg: dict) -> float:
    """`pairs` attended pairs summed over layers, per-head form."""
    return float(pairs) * cfg["num_attention_heads"] * (
        2 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
        + 2 * cfg["v_head_dim"])


def decode_attention_flops(cells: int, cfg: dict) -> float:
    """`cells` attended cells summed over layers, absorbed form."""
    return float(cells) * cfg["num_attention_heads"] * (
        2 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
        + 2 * cfg["kv_lora_rank"])


def projection_params(cfg: dict) -> int:
    """An attention layer's matrices: query, down- and up-projection of
    the latent, output (94.6 M at the published widths)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, value, latent = (
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        cfg["kv_lora_rank"])
    return (d * h * (nope + rope) + d * (latent + rope)
            + latent * h * (nope + value) + h * value * d)


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def token_flops_outside_experts(cfg: dict) -> float:
    """Per token of a forward through all layers: the attention's four
    projections, the dense layers' MLP, and in a routed layer the router
    (over every published expert) and the shared expert; 2 FLOP a
    parameter. The head is left out: a wave applies it at one position."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    routed = cfg["num_hidden_layers"] - dense
    per_routed = (d * cfg["published"]["num_experts"]
                  + cfg["num_shared_experts"] * expert_params(cfg))
    return 2.0 * (cfg["num_hidden_layers"] * projection_params(cfg)
                  + dense * 3 * d * cfg["intermediate_size"]
                  + routed * per_routed)


def prefill_flops(cfg: dict, tokens: int, pairs: int,
                  held_pairs: float) -> float:
    """The model FLOPs of prefilling `tokens` real tokens that attended
    `pairs` pairs (summed over layers) and routed `held_pairs` (token,
    choice) pairs to experts held here."""
    return (tokens * token_flops_outside_experts(cfg)
            + prefill_attention_flops(pairs, cfg)
            + held_pairs * 2.0 * expert_params(cfg))
