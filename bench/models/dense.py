"""The dense family: grouped-query (or full multi-head) attention, rotary
positions, RMSNorm and a SwiGLU MLP, from a Hugging Face configuration
file (Mistral-7B, the Llama-architecture DeepSeek-LLM-7B).

Its builder's parts: the engine's ``ModelConfig`` for the file, the
weights made from the seed in the engine's layout, and the operations and
bytes its served work needs (``bench/work.py`` dispatches to them).

The weights: each published value is a bfloat16 number, as in the
published checkpoints, made in float32 (on the TPU at 2+ slots the engine
then holds its matmul weights in bfloat16, an exact cast).  They are laid
out as ``repro.models.lm`` loads them (layers stacked under
``stage0/b0``), with two conventions of that layout, both exact in
float32: the engine multiplies embedding rows by ``sqrt(hidden_size)`` and
applies RMSNorm weights as ``1 + stored``.  So the published embedding is
``stored * sqrt(hidden_size)`` and a published norm weight is ``1 +
stored``; the plain reference (``bench/references/llama.py``) reads the
same arrays that way.

The counts are of what a request needs, not of what the program happens
to move: the weights read once per step, the keys and values of the
positions a session actually holds, one new row per active slot, each
value at ``nbytes``, the configuration's published width.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

SUPPORTED = {("silu", False)}       # (hidden_act, tie_word_embeddings)


def model_config(conf: dict) -> ModelConfig:
    """The engine's ModelConfig for a dense GQA / SwiGLU configuration file
    (Hugging Face keys)."""
    key = (conf["hidden_act"], conf["tie_word_embeddings"])
    if key not in SUPPORTED or conf.get("sliding_window"):
        raise ValueError(f"{conf['name']}: the engine path served here is "
                         f"full attention with SwiGLU and untied "
                         f"embeddings, not {key}")
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        activation="swiglu", rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]), tie_embeddings=False)


def bf16(x: jax.Array) -> jax.Array:
    """``x`` rounded to the nearest bfloat16 number, held in float32."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def weights(cfg: ModelConfig, key: jax.Array) -> dict:
    L, d, ff, V = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    ks = iter(jax.random.split(key, 12))

    def normal(shape, std):
        return bf16(jax.random.normal(next(ks), shape, jnp.float32) * std)

    def norm(shape):
        # the published weight, 1 + stored, is the bfloat16 number
        return bf16(1.0 + jax.random.normal(next(ks), shape,
                                            jnp.float32) * 0.1) - 1.0

    # the published embedding, stored * sqrt(d), is the bfloat16 number
    # (exactly so where sqrt(d) is a power of two: 64 in both configs)
    embed = bf16(jax.random.normal(next(ks), (V, d), jnp.float32))
    return {
        "embed": embed / math.sqrt(d),
        "final_norm": norm((d,)),
        "head": normal((d, V), d ** -0.5),
        "stage0": {"b0": {
            "ln1": norm((L, d)),
            "ln2": norm((L, d)),
            "mixer": {"wq": normal((L, d, q), d ** -0.5),
                      "wk": normal((L, d, kv), d ** -0.5),
                      "wv": normal((L, d, kv), d ** -0.5),
                      "wo": normal((L, q, d), q ** -0.5)},
            "mlp": {"wi_gate": normal((L, d, ff), d ** -0.5),
                    "wi_up": normal((L, d, ff), d ** -0.5),
                    "wo": normal((L, ff, d), ff ** -0.5)}}},
    }


def param_count(cfg: ModelConfig) -> dict:
    """Parameters by part: what one token touches in each."""
    d, ff = cfg.d_model, cfg.d_ff
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return {"layers": cfg.n_layers * layer, "head": d * cfg.vocab_size}


def kv_bytes_per_position(cfg: ModelConfig, nbytes: int) -> int:
    """Keys and values of one position across the layers."""
    return cfg.n_layers * 2 * cfg.n_kv_heads * cfg.head_dim * nbytes


def attention_flops(cfg: ModelConfig, queries: int, keys: int) -> float:
    """Scores and weighted values for ``queries`` positions over ``keys``
    positions each."""
    return 4.0 * cfg.n_layers * cfg.n_heads * cfg.head_dim * queries * keys


def decode_step(cfg: ModelConfig, n_active: int, held: int,
                nbytes: int) -> tuple:
    """(flops, bytes) of one decode step of ``n_active`` slots holding
    ``held`` positions between them: every layer weight and the head read
    once, each slot's held keys and values read, one new row written."""
    p = param_count(cfg)
    flops = (2.0 * (p["layers"] + p["head"]) * n_active
             + attention_flops(cfg, 1, 1) * (held + n_active))
    byts = ((p["layers"] + p["head"]) * nbytes
            + n_active * cfg.d_model * nbytes           # embedding rows
            + kv_bytes_per_position(cfg, nbytes) * (held + n_active))
    return flops, byts


def prefill_flops(cfg: ModelConfig, n: int) -> float:
    """One prompt of ``n`` tokens: every layer over every token, causal
    attention, and the head for the last position only."""
    p = param_count(cfg)
    return (2.0 * p["layers"] * n + attention_flops(cfg, n, n) / 2
            + 2.0 * p["head"])
