"""Operations and bytes that the served work needs, computed from shapes.

These count what a request needs, not what the program happens to move:
the weights read once per step, the keys and values of the positions a
session actually holds, one new row per active slot.  float32 everywhere,
as the configurations state.
"""
from __future__ import annotations

from bench.model import param_count

F32 = 4


def kv_bytes_per_position(cfg) -> int:
    """Keys and values of one position across the layers."""
    return cfg.n_layers * 2 * cfg.n_kv_heads * cfg.head_dim * F32


def attention_flops(cfg, queries: int, keys: int) -> float:
    """Scores and weighted values for ``queries`` positions over ``keys``
    positions each."""
    return 4.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim * queries * keys


def decode_step(cfg, n_active: int, held: int) -> tuple:
    """(flops, bytes) of one decode step of ``n_active`` slots holding
    ``held`` positions between them: every layer weight and the head read
    once, each slot's held keys and values read, one new row written."""
    p = param_count(cfg)
    flops = (2.0 * (p["layers"] + p["head"]) * n_active
             + attention_flops(cfg, 1, 1) * (held + n_active))
    byts = ((p["layers"] + p["head"]) * F32
            + n_active * cfg.d_model * F32             # embedding rows
            + kv_bytes_per_position(cfg) * (held + n_active))
    return flops, byts


def prefill_flops(cfg, n: int) -> float:
    """One prompt of ``n`` tokens: every layer over every token, causal
    attention, and the head for the last position only."""
    p = param_count(cfg)
    return (2.0 * p["layers"] * n + attention_flops(cfg, n, n) / 2
            + 2.0 * p["head"])


def decode_token_flops(cfg, position: int) -> float:
    """One decoded token at ``position`` (it attends over position + 1)."""
    p = param_count(cfg)
    return (2.0 * (p["layers"] + p["head"])
            + attention_flops(cfg, 1, position + 1))
