"""A configuration file's model as the engine serves it, and its weights.

The weights are the benchmark's, made from the run's seed on the device in
one jitted call.  Each published value is a bfloat16 number, as in the
published checkpoints, held in float32 as the engine serves it.  They are
stored in
the layout the engine loads (``repro.models.lm``: layers stacked under
``stage0/b0``), with two conventions of that layout, both exact in float32:
the engine multiplies embedding rows by ``sqrt(hidden_size)`` and applies
RMSNorm weights as ``1 + stored``.  So the published embedding is
``stored * sqrt(hidden_size)`` and a published norm weight is
``1 + stored``; the plain reference (``bench/references``) reads the same
arrays that way.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import lm

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


def _weights(cfg: ModelConfig, key: jax.Array) -> dict:
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


def init_weights(cfg: ModelConfig, seed_key: jax.Array) -> dict:
    """All weights in one jitted call on the default device.  Raises if the
    engine's parameter layout has changed from the one built here."""
    want = jax.eval_shape(lambda: lm.init_lm(cfg, jax.random.key(0)))
    got = jax.eval_shape(lambda: _weights(cfg, seed_key))
    shape = lambda t: jax.tree.map(lambda s: (s.shape, s.dtype), t)
    if shape(want) != shape(got):
        raise ValueError("the engine's parameter layout differs from the "
                         "benchmark's weight layout")
    return jax.jit(_weights, static_argnums=0)(cfg, seed_key)


def param_count(cfg: ModelConfig) -> dict:
    """Parameters by part: what one token touches in each."""
    d, ff = cfg.d_model, cfg.d_ff
    q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    layer = d * q + 2 * d * kv + q * d + 3 * d * ff
    return {"layers": cfg.n_layers * layer, "head": d * cfg.vocab_size,
            "embed": d * cfg.vocab_size}
