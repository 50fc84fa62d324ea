"""Operations and bytes a decoder of gated delta-rule layers, output-gated
attention layers and routed experts needs, from its configuration's keys:
the MATHEMATICS, whatever implements it (a chunked prefill that solves
64 x 64 systems and multiplies in six passes, or a tick that reads every
cell of a slab and masks, does more and is credited no more).

The delta rule is counted in its recurrence: per token and value head a
decay of the state (K V), a read at the key (2 K V), a write (2 K V) and
an output (2 K V), 7 K V FLOP, 114,688 at 128 x 128 (the chunked form
spends about 139,000). Attention: per attended (query, cell) pair and
head 2 D in the score and 2 D in the sum, 16,384 a pair and layer at 16
heads of 256; a causal prefill of n tokens attends n (n + 1) / 2 pairs a
layer.
"""

from __future__ import annotations

BYTES = 2       # bfloat16 parameters


def layers(cfg: dict) -> tuple:
    """(delta-rule layers, attention layers) of the depth as run."""
    depth, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    attention = sum(1 for l in range(depth) if (l + 1) % every == 0)
    return depth - attention, attention


def delta_widths(cfg: dict) -> tuple:
    """(key width, value width): q and k, v and z of a delta-rule layer."""
    return (cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"],
            cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"])


def delta_projection_params(cfg: dict) -> int:
    """A delta-rule layer's matrices: [q, k, v, z], [b, a] and the output
    (33,685,504 at the published widths)."""
    d = cfg["hidden_size"]
    kw, vw = delta_widths(cfg)
    return (d * (2 * kw + 2 * vw) + d * 2 * cfg["linear_num_value_heads"]
            + vw * d)


def delta_small_params(cfg: dict) -> int:
    """Its taps, A_log, dt_bias and the gain of its norm (32,960)."""
    kw, vw = delta_widths(cfg)
    return (cfg["linear_conv_kernel_dim"] * (2 * kw + vw)
            + 2 * cfg["linear_num_value_heads"]
            + cfg["linear_value_head_dim"])


def attention_projection_params(cfg: dict) -> int:
    """An attention layer's matrices: the query with its gate, key, value,
    output (27,262,976)."""
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return d * h * 2 * hd + 2 * d * kv * hd + h * hd * d


def expert_params(cfg: dict) -> int:
    """One routed expert's three matrices (3,145,728)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_and_shared_params(cfg: dict) -> int:
    """A layer's router over every published expert, its shared expert and
    that expert's gate (4,196,352)."""
    d = cfg["hidden_size"]
    return (d * cfg["published"]["num_experts"]
            + 3 * d * cfg["shared_expert_intermediate_size"] + d)


def num_params(cfg: dict) -> int:
    """Every parameter this chip holds (3,677,613,120): per layer the held
    experts, the router and shared expert and two norms; the mixers (an
    attention layer's q and k norms with it); embedding, head and the
    final norm."""
    d = cfg["hidden_size"]
    delta, attention = layers(cfg)
    per_layer = (cfg["num_experts"] * expert_params(cfg)
                 + router_and_shared_params(cfg) + 2 * d)
    return ((delta + attention) * per_layer
            + delta * (delta_projection_params(cfg) + delta_small_params(cfg))
            + attention * (attention_projection_params(cfg)
                           + 2 * cfg["head_dim"])
            + 2 * cfg["vocab_size"] * d + d)


def dense_bytes(cfg: dict) -> int:
    """What every decode tick reads of the parameters whatever it routes:
    all but the routed experts and the embedding (a tick gathers a row of
    it a token), the head among them."""
    d = cfg["hidden_size"]
    delta, attention = layers(cfg)
    return BYTES * (num_params(cfg) - cfg["vocab_size"] * d
                    - (delta + attention) * cfg["num_experts"]
                    * expert_params(cfg))


def decode_least_bytes(cfg: dict, ticks: int, experts_touched: int,
                       kv_cell_bytes: int, state_bytes: int) -> float:
    """The bytes `ticks` decode ticks cannot avoid moving: the dense
    weights a tick; of the held experts those that received a pair
    (`experts_touched`, the device's count summed over layers and ticks);
    the live K/V cells read (`kv_cell_bytes`, summed over ticks); the
    active rows' state read and written (`state_bytes`, summed over
    ticks, twice)."""
    return (float(ticks) * dense_bytes(cfg)
            + float(experts_touched) * BYTES * expert_params(cfg)
            + float(kv_cell_bytes) + 2.0 * float(state_bytes))


def token_flops_outside_experts(cfg: dict) -> float:
    """Per token of a forward through all layers: the mixers' projections
    and taps, the delta rule itself, the routers and shared experts; 2
    FLOP a parameter. The head is left out (a wave applies it at one
    position) and attention's pairs are counted apart."""
    delta, attention = layers(cfg)
    kw, vw = delta_widths(cfg)
    rule = (7 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"])
    taps = 2 * cfg["linear_conv_kernel_dim"] * (2 * kw + vw)
    return (delta * (2.0 * delta_projection_params(cfg) + taps + rule)
            + attention * 2.0 * attention_projection_params(cfg)
            + (delta + attention) * 2.0 * router_and_shared_params(cfg))


def attention_flops(pairs: int, cfg: dict) -> float:
    """`pairs` attended (query, cell) pairs summed over layers."""
    return float(pairs) * cfg["num_attention_heads"] * 4 * cfg["head_dim"]


def prefill_flops(cfg: dict, tokens: int, pairs: int,
                  held_pairs: float) -> float:
    """The model FLOPs of prefilling `tokens` real tokens that attended
    `pairs` pairs (summed over the attention layers) and routed
    `held_pairs` (token, choice) pairs to experts held here."""
    return (tokens * token_flops_outside_experts(cfg)
            + attention_flops(pairs, cfg)
            + held_pairs * 2.0 * expert_params(cfg))
