"""A builder for a latent-attention (MLA), mixture-of-experts model in
DeepSeek-V2's form, as a later configuration would add it under
``bench/models/``: the engine's ``ModelConfig`` from the Hugging Face keys,
weights in the engine's layout, and counts of its own.  The tests copy it
into a copy of the benchmark; no cell runs it.

What differs from the dense family's counts: one position holds the
latent ``kv_lora_rank + qk_rope_head_dim`` values a layer, not keys and
values per head; decode attends over the latent (absorbed) form; and a
step's tokens reach ``num_experts_per_tok`` routed experts each, at most
all of them, plus the shared ones, not every expert.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.models import dense
from repro.configs.base import ModelConfig


def model_config(conf: dict) -> ModelConfig:
    return ModelConfig(
        name=conf["name"], family="moe",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["qk_nope_head_dim"] + conf["qk_rope_head_dim"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        attn_kind="mla", activation="swiglu",
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]),
        kv_lora_rank=conf["kv_lora_rank"], q_lora_rank=conf["q_lora_rank"],
        qk_nope_dim=conf["qk_nope_head_dim"],
        qk_rope_dim=conf["qk_rope_head_dim"],
        v_head_dim=conf["v_head_dim"], n_experts=conf["n_routed_experts"],
        n_shared_experts=conf["n_shared_experts"],
        top_k=conf["num_experts_per_tok"],
        moe_d_ff=conf["moe_intermediate_size"],
        moe_every=conf["moe_layer_freq"])


def weights(cfg: ModelConfig, key: jax.Array) -> dict:
    L, d, H, V = cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.vocab_size
    ql, kl, r = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_dim
    nope, v = cfg.qk_nope_dim, cfg.v_head_dim
    E, f = cfg.n_experts, cfg.moe_d_ff
    s = cfg.n_shared_experts * f
    ks = iter(jax.random.split(key, 16))
    normal = lambda shape, fan_in: dense.bf16(
        jax.random.normal(next(ks), shape, jnp.float32) * fan_in ** -0.5)
    zeros = lambda *shape: jnp.zeros(shape, jnp.float32)
    return {
        "embed": normal((V, d), d), "final_norm": zeros(d),
        "head": normal((d, V), d),
        "stage0": {"b0": {
            "ln1": zeros(L, d), "ln2": zeros(L, d),
            "mixer": {"q_a": normal((L, d, ql), d), "q_norm": zeros(L, ql),
                      "q_b": normal((L, ql, H * (nope + r)), ql),
                      "kv_a": normal((L, d, kl + r), d),
                      "kv_norm": zeros(L, kl),
                      "kv_b": normal((L, kl, H * (nope + v)), kl),
                      "wo": normal((L, H * v, d), H * v)},
            "mlp": {"router": normal((L, d, E), d),
                    "wi_gate": normal((L, E, d, f), d),
                    "wi_up": normal((L, E, d, f), d),
                    "wo": normal((L, E, f, d), f),
                    "shared": {"wi_gate": normal((L, d, s), d),
                               "wi_up": normal((L, d, s), d),
                               "wo": normal((L, s, d), s)}}}},
    }


def _attention_params(cfg: ModelConfig) -> int:
    d, H, kl = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    return (d * cfg.q_lora_rank
            + cfg.q_lora_rank * H * (cfg.qk_nope_dim + cfg.qk_rope_dim)
            + d * (kl + cfg.qk_rope_dim)
            + kl * H * (cfg.qk_nope_dim + cfg.v_head_dim)
            + H * cfg.v_head_dim * d)


def param_count(cfg: ModelConfig, experts: int) -> dict:
    """Parameters by part that tokens reaching ``experts`` routed experts
    of each layer touch."""
    expert = 3 * cfg.d_model * cfg.moe_d_ff
    layer = (_attention_params(cfg) + cfg.d_model * cfg.n_experts
             + expert * (experts + cfg.n_shared_experts))
    return {"layers": cfg.n_layers * layer,
            "head": cfg.d_model * cfg.vocab_size}


def kv_bytes_per_position(cfg: ModelConfig, nbytes: int) -> int:
    """The latent and the rotary key of one position across the layers."""
    return cfg.n_layers * (cfg.kv_lora_rank + cfg.qk_rope_dim) * nbytes


def decode_step(cfg: ModelConfig, n_active: int, held: int,
                nbytes: int) -> tuple:
    """Absorbed attention over the latent cache; the experts that
    ``n_active`` tokens reach read once, every token's own through its
    experts."""
    reached = param_count(cfg, min(cfg.n_experts, n_active * cfg.top_k))
    each = param_count(cfg, cfg.top_k)
    per_key = (2.0 * cfg.n_layers * cfg.n_heads
               * (2 * cfg.kv_lora_rank + cfg.qk_rope_dim))
    flops = (2.0 * (each["layers"] + each["head"]) * n_active
             + per_key * (held + n_active))
    byts = ((reached["layers"] + reached["head"]) * nbytes
            + n_active * cfg.d_model * nbytes
            + kv_bytes_per_position(cfg, nbytes) * (held + n_active))
    return flops, byts


def prefill_flops(cfg: ModelConfig, n: int) -> float:
    """Keys and values expanded per head, causal; the head for the last
    position."""
    p = param_count(cfg, cfg.top_k)
    pair = (2.0 * cfg.n_layers * cfg.n_heads
            * (cfg.qk_nope_dim + cfg.qk_rope_dim + cfg.v_head_dim))
    return 2.0 * p["layers"] * n + pair * n * n / 2 + 2.0 * p["head"]
