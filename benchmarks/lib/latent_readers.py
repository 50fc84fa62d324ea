"""What the readers of a cell whose attention layers cache latent cells
share: the `.mla` metrics are listed for that cell alone, and each reads
nothing (None, and raises nothing) from a program that does not count
latent cells, whichever cell it is handed."""

from __future__ import annotations

from benchmarks.lib import readers


def counted(obs: dict) -> bool:
    """Did the program count latent cells (`stats()`: `latent_cells_read`,
    `capacity.LatentCapacityLedger`)?"""
    return readers.counter(obs, "latent_cells_read") is not None


def cell_bytes(cfg: dict) -> int:
    """One layer's cached cell of one position: the latent and the one
    rotary key, in bfloat16 (1,152 B at 512 + 64)."""
    return 2 * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def routed_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
