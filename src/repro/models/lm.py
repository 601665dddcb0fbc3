"""Composable LM assembly for all assigned architectures.

A model is a sequence of *stages*; each stage scans a repeating *group* of
layers (the smallest period of the per-layer kind sequence), so heterogeneous
stacks (gemma3's 5-local:1-global, jamba's 7-mamba:1-attn with alternating
MoE) compile to small HLO with stacked parameters, exactly like uniform
stacks.

Three modes share one code path:
  train    — causal over the sequence, no cache
  prefill  — train math + cache writes (tail-slice for windowed layers)
  decode   — single token, attends over the cache

Caches: full KV / ring-buffer window KV / MLA compressed / Mamba state /
RWKV state / enc-dec cross-KV.  All functional (pytrees in, pytrees out).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import moe as MOE
from repro.models import rwkv as R
from repro.models import ssm as S
from repro.models.layers import embed, init_embed, init_mlp, init_rms, mlp, rms_norm, unembed
from repro.models.sharding import constrain

LayerSpec = Tuple[str, str]     # (mixer_kind, mlp_kind)


# ---------------------------------------------------------------------------
# Stage decomposition: smallest repeating pattern + tail.
# ---------------------------------------------------------------------------

def stages_of(cfg: ModelConfig) -> List[Tuple[int, Tuple[LayerSpec, ...]]]:
    kinds = list(zip(cfg.layer_kinds(), cfg.mlp_kinds()))
    n = len(kinds)
    for p in range(1, n + 1):
        if all(kinds[i] == kinds[i % p] for i in range(n)):
            reps, tail = n // p, n % p
            out = [(reps, tuple(kinds[:p]))]
            if tail:
                out.append((1, tuple(kinds[reps * p:])))
            return out
    return [(1, tuple(kinds))]


# ---------------------------------------------------------------------------
# Per-layer init.
# ---------------------------------------------------------------------------

def _init_mixer(cfg: ModelConfig, key: jax.Array, kind: str, dtype) -> Dict:
    if kind in ("attn_full", "attn_local"):
        return A.init_gqa_params(key, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                                 cfg.head_dim, cfg.qkv_bias, dtype)
    if kind == "mla":
        return A.init_mla_params(key, cfg.d_model, cfg.n_heads, cfg.q_lora_rank,
                                 cfg.kv_lora_rank, cfg.qk_nope_dim,
                                 cfg.qk_rope_dim, cfg.v_head_dim, dtype)
    if kind == "mamba":
        return S.init_mamba_params(key, cfg.d_model, cfg.d_state, cfg.d_conv,
                                   cfg.expand, dtype)
    if kind == "rwkv":
        return R.init_rwkv_params(key, cfg.d_model, cfg.d_ff, dtype)
    raise ValueError(kind)


def _init_block(cfg: ModelConfig, key: jax.Array, spec: LayerSpec,
                cross: bool, dtype) -> Dict:
    mixer_kind, mlp_kind = spec
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": init_rms(cfg.d_model),
                         "mixer": _init_mixer(cfg, k1, mixer_kind, dtype)}
    if mlp_kind == "dense":
        p["ln2"] = init_rms(cfg.d_model)
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dtype)
    elif mlp_kind == "moe":
        p["ln2"] = init_rms(cfg.d_model)
        p["mlp"] = MOE.init_moe_params(k2, cfg.d_model, cfg.n_experts,
                                       cfg.moe_d_ff, cfg.n_shared_experts,
                                       cfg.activation, dtype)
    elif mlp_kind == "rwkv_cm":
        p["ln2"] = init_rms(cfg.d_model)          # channel-mix params live in mixer
    if cross:
        p["ln_cross"] = init_rms(cfg.d_model)
        p["cross"] = A.init_cross_params(k3, cfg.d_model, cfg.n_heads,
                                         cfg.head_dim, dtype)
    return p


def _init_stage(cfg: ModelConfig, key: jax.Array, reps: int,
                group: Tuple[LayerSpec, ...], cross: bool, dtype) -> Dict:
    def one(k):
        ks = jax.random.split(k, len(group))
        return {f"b{j}": _init_block(cfg, ks[j], spec, cross, dtype)
                for j, spec in enumerate(group)}
    return jax.vmap(one)(jax.random.split(key, reps))


def init_lm(cfg: ModelConfig, key: jax.Array, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": init_embed(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": init_rms(cfg.d_model),
    }
    for i, (reps, group) in enumerate(stages_of(cfg)):
        params[f"stage{i}"] = _init_stage(cfg, ks[2 + i], reps, group,
                                          cross=cfg.encdec, dtype=dtype)
    if not cfg.tie_embeddings:
        params["head"] = init_embed(ks[1], cfg.vocab_size, cfg.d_model,
                                    dtype).T
    if cfg.encdec:
        enc_spec: LayerSpec = ("attn_full", "dense")
        params["encoder"] = _init_stage(cfg, ks[6], cfg.n_enc_layers,
                                        (enc_spec,), cross=False, dtype=dtype)
        params["enc_norm"] = init_rms(cfg.d_model)
    return params


def bf16_matmul_weights(cfg: ModelConfig, params: Dict) -> Dict:
    """``params`` with the dense path's matmul-only weights in bfloat16:
    each attention layer's ``wq wk wv wo``, each dense MLP's ``wi_gate
    wi_up wo`` and an untied ``head``.  A float32 activation meets them in
    a mixed ``x @ w``, which JAX hands to the dot as it is.  The embedding
    table (gathered, and the head too when tied), norms, biases, and the
    weights of MLA, MoE, mamba, RWKV and cross-attention stay as given."""
    cast = lambda d, keys: {k: v.astype(jnp.bfloat16) if k in keys else v
                            for k, v in d.items()}
    out = dict(params)
    for i, (_, group) in enumerate(stages_of(cfg)):
        stage = dict(params[f"stage{i}"])
        for j, (mixer_kind, mlp_kind) in enumerate(group):
            block = dict(stage[f"b{j}"])
            if mixer_kind in ("attn_full", "attn_local"):
                block["mixer"] = cast(block["mixer"], ("wq", "wk", "wv", "wo"))
            if mlp_kind == "dense":
                block["mlp"] = cast(block["mlp"], ("wi_gate", "wi_up", "wo"))
            stage[f"b{j}"] = block
        out[f"stage{i}"] = stage
    if not cfg.tie_embeddings:
        out["head"] = params["head"].astype(jnp.bfloat16)
    return out


# ---------------------------------------------------------------------------
# Cache construction.
# ---------------------------------------------------------------------------

def _block_cache(cfg: ModelConfig, spec: LayerSpec, reps: int, batch: int,
                 max_len: int, enc_len: int, dtype) -> Dict:
    mixer_kind, _ = spec
    c: Dict[str, Any] = {}
    if mixer_kind in ("attn_full", "attn_local"):
        L = max_len if (mixer_kind == "attn_full" or cfg.window == 0) \
            else min(max_len, cfg.window)
        c["k"] = jnp.zeros((reps, batch, L, cfg.n_kv_heads, cfg.head_dim), dtype)
        c["v"] = jnp.zeros((reps, batch, L, cfg.n_kv_heads, cfg.head_dim), dtype)
        c["pos"] = jnp.full((reps, batch, L), 2**30, jnp.int32)
        if dtype == jnp.int8:      # quantised KV: per-token-per-head scales
            c["k_scale"] = jnp.zeros((reps, batch, L, cfg.n_kv_heads),
                                     jnp.float32)
            c["v_scale"] = jnp.zeros((reps, batch, L, cfg.n_kv_heads),
                                     jnp.float32)
    elif mixer_kind == "mla":
        c["cc"] = jnp.zeros((reps, batch, max_len, cfg.kv_lora_rank), dtype)
        c["cr"] = jnp.zeros((reps, batch, max_len, cfg.qk_rope_dim), dtype)
        c["pos"] = jnp.full((reps, batch, max_len), 2**30, jnp.int32)
        if dtype == jnp.int8:
            c["cc_scale"] = jnp.zeros((reps, batch, max_len), jnp.float32)
            c["cr_scale"] = jnp.zeros((reps, batch, max_len), jnp.float32)
    elif mixer_kind == "mamba":
        st = S.init_mamba_state(batch, cfg.d_model, cfg.d_state, cfg.d_conv,
                                cfg.expand, dtype)
        c["conv"] = jnp.zeros((reps,) + st.conv.shape, dtype)
        c["ssm"] = jnp.zeros((reps,) + st.ssm.shape, jnp.float32)
    elif mixer_kind == "rwkv":
        st = R.init_rwkv_state(batch, cfg.d_model, dtype)
        c["att_shift"] = jnp.zeros((reps,) + st.att_shift.shape, dtype)
        c["ffn_shift"] = jnp.zeros((reps,) + st.ffn_shift.shape, dtype)
        c["wkv"] = jnp.zeros((reps,) + st.wkv.shape, jnp.float32)
    if cfg.encdec:
        c["enc_k"] = jnp.zeros((reps, batch, enc_len, cfg.n_heads, cfg.head_dim), dtype)
        c["enc_v"] = jnp.zeros((reps, batch, enc_len, cfg.n_heads, cfg.head_dim), dtype)
        c["enc_pos"] = jnp.full((reps, batch, enc_len), 2**30, jnp.int32)
    return c


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               enc_len: int = 0, dtype=jnp.float32) -> Dict:
    cache: Dict[str, Any] = {}
    for i, (reps, group) in enumerate(stages_of(cfg)):
        cache[f"stage{i}"] = {
            f"b{j}": _block_cache(cfg, spec, reps, batch, max_len, enc_len, dtype)
            for j, spec in enumerate(group)}
    return cache


# ---------------------------------------------------------------------------
# Block application (one layer; train/prefill/decode).
# ---------------------------------------------------------------------------

def _apply_block(cfg: ModelConfig, spec: LayerSpec, p: Dict, x: jax.Array,
                 positions: jax.Array, mode: str,
                 cache: Optional[Dict], cache_index,
                 enc_out: Optional[jax.Array],
                 moe_groups: int) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    mixer_kind, mlp_kind = spec
    aux = jnp.zeros((), jnp.float32)
    B, Sq, _ = x.shape
    new_cache: Dict[str, Any] = {}
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    window = cfg.window if mixer_kind == "attn_local" else 0
    tok_pos = positions[0] if cfg.mrope else positions

    if mixer_kind in ("attn_full", "attn_local"):
        kv = None
        idx = None
        kv_scales = None
        if cache is not None and mode == "decode":
            L = cache["k"].shape[1]
            idx = cache_index % L
            kv = (cache["k"], cache["v"], cache["pos"])
            if "k_scale" in cache:
                kv_scales = (cache["k_scale"], cache["v_scale"])
        y, newkv = A.gqa_block(
            p["mixer"], h, positions, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads,
            head_dim=cfg.head_dim, rope_theta=cfg.rope_theta, mrope=cfg.mrope,
            window=window, block=cfg.attn_block, kv_cache=kv, cache_index=idx,
            kv_scales=kv_scales)
        if cache is not None and mode == "decode":
            new_cache.update(k=newkv[0], v=newkv[1], pos=newkv[2])
            if newkv[3] is not None:
                new_cache.update(k_scale=newkv[3][0], v_scale=newkv[3][1])
        elif cache is not None:  # prefill: recompute K/V tail into the cache
            L = cache["k"].shape[1]
            k_, v_ = h @ p["mixer"]["wk"], h @ p["mixer"]["wv"]
            if "bk" in p["mixer"]:
                k_, v_ = k_ + p["mixer"]["bk"], v_ + p["mixer"]["bv"]
            k_ = k_.reshape(B, Sq, cfg.n_kv_heads, cfg.head_dim)
            v_ = v_.reshape(B, Sq, cfg.n_kv_heads, cfg.head_dim)
            if cfg.mrope:
                k_ = A.apply_mrope(k_, positions, cfg.rope_theta)
            else:
                k_ = A.apply_rope(k_, positions, cfg.rope_theta)
            take = min(Sq, L)
            # Ring alignment: token t lands in slot t % L, so later decode
            # steps (slot = pos % L) overwrite the oldest entry first.
            roll = (Sq - take) % L
            upd = lambda c, t: jax.lax.dynamic_update_slice(
                c, jnp.roll(t[:, -take:], roll, axis=1), (0,) * c.ndim)
            new_cache.update(
                k=upd(cache["k"], k_), v=upd(cache["v"], v_),
                pos=upd(cache["pos"], tok_pos))
    elif mixer_kind == "mla":
        kv = None
        idx = None
        kv_scales = None
        if cache is not None and mode == "decode":
            idx = cache_index
            kv = (cache["cc"], cache["cr"], cache["pos"])
            if "cc_scale" in cache:
                kv_scales = (cache["cc_scale"], cache["cr_scale"])
        y, newkv = A.mla_block(
            p["mixer"], h, positions, n_heads=cfg.n_heads,
            q_lora=cfg.q_lora_rank, kv_lora=cfg.kv_lora_rank,
            qk_nope=cfg.qk_nope_dim, qk_rope=cfg.qk_rope_dim,
            v_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta,
            block=cfg.attn_block, kv_cache=kv, cache_index=idx,
            kv_scales=kv_scales)
        if cache is not None and mode == "decode":
            new_cache.update(cc=newkv[0], cr=newkv[1], pos=newkv[2])
            if newkv[3] is not None:
                new_cache.update(cc_scale=newkv[3][0], cr_scale=newkv[3][1])
        elif cache is not None:
            _, _, c_kv, k_rope = A._mla_qkr(
                p["mixer"], h, positions, cfg.n_heads, cfg.qk_nope_dim,
                cfg.qk_rope_dim, cfg.kv_lora_rank, cfg.rope_theta)
            new_cache.update(
                cc=jax.lax.dynamic_update_slice(cache["cc"], c_kv, (0, 0, 0)),
                cr=jax.lax.dynamic_update_slice(cache["cr"], k_rope, (0, 0, 0)),
                pos=jax.lax.dynamic_update_slice(cache["pos"], tok_pos, (0, 0)))
    elif mixer_kind == "mamba":
        st = None
        if cache is not None:
            st = S.MambaState(conv=cache["conv"], ssm=cache["ssm"])
        y, new_st = S.mamba_block(p["mixer"], h, d_state=cfg.d_state,
                                  d_conv=cfg.d_conv, expand=cfg.expand,
                                  chunk=cfg.scan_chunk, state=st)
        if cache is not None:
            new_cache.update(conv=new_st.conv, ssm=new_st.ssm)
    elif mixer_kind == "rwkv":
        st = None
        if cache is not None:
            st = R.RWKVState(att_shift=cache["att_shift"],
                             ffn_shift=cache["ffn_shift"], wkv=cache["wkv"])
        y, new_st = R.rwkv_time_mix(p["mixer"], h, chunk=cfg.scan_chunk,
                                    state=st)
        if cache is not None:
            new_cache.update(att_shift=new_st[0], wkv=new_st[1])
    else:
        raise ValueError(mixer_kind)
    x = x + y

    if cfg.encdec:
        hc = rms_norm(x, p["ln_cross"], cfg.norm_eps)
        if cache is not None and mode == "decode":
            enc_k, enc_v, enc_pos = cache["enc_k"], cache["enc_v"], cache["enc_pos"]
        else:
            enc_k, enc_v = A.encode_kv(p["cross"], enc_out, cfg.n_heads,
                                       cfg.head_dim)
            enc_pos = jnp.zeros(enc_out.shape[:2], jnp.int32)
            if cache is not None:
                new_cache.update(enc_k=enc_k, enc_v=enc_v, enc_pos=enc_pos)
        yc = A.cross_block(p["cross"], hc, (enc_k, enc_v),
                           enc_pos == 0, n_heads=cfg.n_heads,
                           head_dim=cfg.head_dim)
        x = x + yc
        if cache is not None and mode == "decode":
            new_cache.update(enc_k=enc_k, enc_v=enc_v, enc_pos=enc_pos)

    if mlp_kind == "dense":
        x = x + mlp(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps),
                    cfg.activation)
    elif mlp_kind == "moe":
        y2, aux = MOE.moe_block(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps),
                                top_k=cfg.top_k, n_groups=moe_groups,
                                capacity_factor=cfg.capacity_factor,
                                activation=cfg.activation)
        x = x + y2
    elif mlp_kind == "rwkv_cm":
        st = None
        if cache is not None:
            st = R.RWKVState(att_shift=cache.get("att_shift"),
                             ffn_shift=cache["ffn_shift"], wkv=cache.get("wkv"))
        y2, new_shift = R.rwkv_channel_mix(
            p["mixer"], rms_norm(x, p["ln2"], cfg.norm_eps), state=st)
        x = x + y2
        if cache is not None:
            new_cache.update(ffn_shift=new_shift)

    x = constrain(x, "batch", "seq_sp", None)
    return x, (new_cache if cache is not None else None), aux


# ---------------------------------------------------------------------------
# Stage runner (scan over the repeating group).
# ---------------------------------------------------------------------------

def _run_stage(cfg: ModelConfig, reps: int, group: Tuple[LayerSpec, ...],
               params: Dict, x: jax.Array, positions: jax.Array, mode: str,
               cache: Optional[Dict], cache_index,
               enc_out: Optional[jax.Array], moe_groups: int):
    def body(carry, xs):
        xc, aux = carry
        p_group, c_group = xs
        new_c_group = {}
        for j, spec in enumerate(group):
            cj = c_group[f"b{j}"] if c_group is not None else None
            xc, ncj, aux_j = _apply_block(cfg, spec, p_group[f"b{j}"], xc,
                                          positions, mode, cj, cache_index,
                                          enc_out, moe_groups)
            if ncj is not None:
                new_c_group[f"b{j}"] = ncj
            aux = aux + aux_j
        return (xc, aux), (new_c_group if c_group is not None else 0)

    if cfg.remat:
        body = jax.checkpoint(body)
    xs = (params, cache)
    (x, aux), new_cache = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux, (new_cache if cache is not None else None)


# ---------------------------------------------------------------------------
# Public API: forward / prefill / decode_step / encode.
# ---------------------------------------------------------------------------

def _default_positions(cfg: ModelConfig, B: int, Sq: int, offset=0):
    pos = jnp.arange(Sq, dtype=jnp.int32)[None, :] + offset
    pos = jnp.broadcast_to(pos, (B, Sq))
    if cfg.mrope:
        pos = jnp.broadcast_to(pos[None], (3, B, Sq))
    return pos


def _encode(cfg: ModelConfig, params: Dict, enc_embeds: jax.Array):
    """Bidirectional encoder over frontend embeddings (audio/vision stub)."""
    B, T, _ = enc_embeds.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    x = enc_embeds

    def body(carry, p_group):
        xc, _ = carry
        p = p_group["b0"]
        h = rms_norm(xc, p["ln1"], cfg.norm_eps)
        q = (h @ p["mixer"]["wq"]).reshape(B, T, cfg.n_heads, cfg.head_dim)
        k = (h @ p["mixer"]["wk"]).reshape(B, T, cfg.n_heads, cfg.head_dim)
        v = (h @ p["mixer"]["wv"]).reshape(B, T, cfg.n_heads, cfg.head_dim)
        q = A.apply_rope(q, pos, cfg.rope_theta)
        k = A.apply_rope(k, pos, cfg.rope_theta)
        out = A.chunked_attention(q, k, v, pos, pos, causal=False,
                                  block=cfg.attn_block)
        xc = xc + out.reshape(B, T, -1) @ p["mixer"]["wo"]
        xc = xc + mlp(p["mlp"], rms_norm(xc, p["ln2"], cfg.norm_eps),
                      cfg.activation)
        return (xc, jnp.zeros((), jnp.float32)), 0

    body = jax.checkpoint(body) if cfg.remat else body
    (x, _), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                             params["encoder"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def forward(cfg: ModelConfig, params: Dict, tokens: jax.Array,
            positions: Optional[jax.Array] = None,
            enc_embeds: Optional[jax.Array] = None,
            cache: Optional[Dict] = None, mode: str = "train",
            moe_groups: int = 0) -> Tuple[jax.Array, jax.Array, Optional[Dict]]:
    """Returns (logits, aux_loss, new_cache)."""
    B, Sq = tokens.shape
    if positions is None:
        positions = _default_positions(cfg, B, Sq)
    x = embed(params["embed"], tokens) * math.sqrt(cfg.d_model)
    enc_out = _encode(cfg, params, enc_embeds) if cfg.encdec else None

    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    for i, (reps, group) in enumerate(stages_of(cfg)):
        ci = cache[f"stage{i}"] if cache is not None else None
        x, aux, nci = _run_stage(cfg, reps, group, params[f"stage{i}"], x,
                                 positions, mode, ci, 0, enc_out, moe_groups)
        aux_total += aux
        if nci is not None:
            new_cache[f"stage{i}"] = nci
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"] if cfg.tie_embeddings else params["head"],
                     x, tied=cfg.tie_embeddings)
    return logits, aux_total, (new_cache if cache is not None else None)


def decode_step(cfg: ModelConfig, params: Dict, cache: Dict,
                tokens: jax.Array, pos: jax.Array,
                moe_groups: int = 0) -> Tuple[jax.Array, Dict]:
    """One decode step.  tokens: (B, 1); pos: scalar int32 (uniform batch
    position; ragged continuous batching uses :func:`decode_step_batched`).
    Returns (logits (B,1,V), new_cache)."""
    B, Sq = tokens.shape
    positions = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32)[None, None], (B, Sq))
    if cfg.mrope:
        positions = jnp.broadcast_to(positions[None], (3, B, Sq))
    x = embed(params["embed"], tokens) * math.sqrt(cfg.d_model)

    new_cache: Dict[str, Any] = {}
    for i, (reps, group) in enumerate(stages_of(cfg)):
        x, _, nci = _run_stage(cfg, reps, group, params[f"stage{i}"], x,
                               positions, "decode", cache[f"stage{i}"],
                               jnp.asarray(pos, jnp.int32), None, moe_groups)
        new_cache[f"stage{i}"] = nci
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"] if cfg.tie_embeddings else params["head"],
                     x, tied=cfg.tie_embeddings)
    return logits, new_cache


def decode_step_batched(cfg: ModelConfig, params: Dict, cache: Dict,
                        tokens: jax.Array, pos: jax.Array, active: jax.Array,
                        moe_groups: int = 0) -> Tuple[jax.Array, Dict]:
    """One continuous-batching decode step: ONE dispatch for a ragged batch.

    tokens: (B,) int32 — last emitted token per slot; pos: (B,) int32 —
    per-slot positions (need not be uniform: each row reads/writes its own
    cache slot); active: (B,) bool — slots currently serving a request.
    Greedy sampling runs in-graph, so the only device→host traffic per step
    is the (B,) next-token vector.  Returns (next_tokens, new_cache);
    next_tokens is -1 for inactive slots, whose cache rows are left bit-exact
    (a suspended slot cannot be corrupted by a stale in-flight row).
    """
    logits, new_cache = decode_logits_batched(cfg, params, cache, tokens,
                                              pos, active, moe_groups)
    nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.where(active, nxt, -1), new_cache


def decode_logits_batched(cfg: ModelConfig, params: Dict, cache: Dict,
                          tokens: jax.Array, pos: jax.Array,
                          active: jax.Array,
                          moe_groups: int = 0) -> Tuple[jax.Array, Dict]:
    """The model half of :func:`decode_step_batched`, before sampling:
    returns (logits (B, V), new_cache) with the same arguments."""
    B = tokens.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    positions = pos[:, None]                                 # (B, 1)
    if cfg.mrope:
        positions = jnp.broadcast_to(positions[None], (3, B, 1))
    x = embed(params["embed"], tokens[:, None]) * math.sqrt(cfg.d_model)

    new_cache: Dict[str, Any] = {}
    for i, (reps, group) in enumerate(stages_of(cfg)):
        x, _, nci = _run_stage(cfg, reps, group, params[f"stage{i}"], x,
                               positions, "decode", cache[f"stage{i}"],
                               pos, None, moe_groups)
        new_cache[f"stage{i}"] = nci
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"] if cfg.tie_embeddings else params["head"],
                     x, tied=cfg.tie_embeddings)
    # Cache leaves are (reps, batch, ...): inactive rows keep their old bits.
    keep = lambda o, n: jnp.where(
        active.reshape((1, B) + (1,) * (n.ndim - 2)), n, o)
    return logits[:, -1, :], jax.tree.map(keep, cache, new_cache)


def prefill(cfg: ModelConfig, params: Dict, tokens: jax.Array,
            cache: Dict, enc_embeds: Optional[jax.Array] = None,
            moe_groups: int = 0,
            positions: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """Prefill the cache.  ``positions`` defaults to arange; bucketed serving
    passes right-padded tokens with sentinel (2**30) positions for the pads,
    which keeps them causally invisible forever (see serve/engine)."""
    logits, _, new_cache = forward(cfg, params, tokens, positions=positions,
                                   cache=cache, enc_embeds=enc_embeds,
                                   mode="prefill", moe_groups=moe_groups)
    return logits, new_cache
