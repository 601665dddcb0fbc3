"""Plain reference forward of a dense decoder with grouped-query attention,
rotary positions (half rotation), RMSNorm and a SwiGLU MLP: Mistral-7B and
the Llama-architecture DeepSeek-LLM-7B, as their published configurations
describe them.

Straightforward ``jax.numpy``: no cache, no batching, no kernels, nothing
imported from the program.  It reads the benchmark's weights as published
weights (``bench/models/dense.py``: embedding ``stored * sqrt(hidden_size)``,
norm weight ``1 + stored``).  One sequence at a time, padded to a fixed
length (causal, so padding at the end changes no earlier position),
attention in blocks of queries, the head in blocks of positions.

``dtype=float32`` with ``precision="highest"`` is the reference.  The
control is one precision below the bfloat16 that the configurations state:
``quant="int8"`` rounds the input and the weight of every linear layer and
of the head to int8 (symmetric; a scale per row of the input and per output
column of the weight), with everything else in bfloat16 at the default
precision.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

Q_BLOCK = 256
HEAD_BLOCK = 256


def _rms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, theta):
    """x: (S, heads, head_dim); rotate the two halves of each head."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(q, k, v, prec):
    """Causal attention; q: (S, H, hd), k/v: (S, K, hd), in query blocks."""
    S, H, hd = q.shape
    K = k.shape[1]
    G = H // K
    qg = q.reshape(S, K, G, hd)
    out = []
    for s0 in range(0, S, Q_BLOCK):
        qb = qg[s0:s0 + Q_BLOCK]
        sc = jnp.einsum("skgd,tkd->kgst", qb, k, precision=prec)
        sc = sc / jnp.asarray(math.sqrt(hd), sc.dtype)
        qpos = jnp.arange(s0, s0 + qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(S)[None, :] <= qpos, sc,
                       jnp.asarray(-jnp.inf, sc.dtype))
        p = jax.nn.softmax(sc, axis=-1)
        out.append(jnp.einsum("kgst,tkd->skgd", p, v, precision=prec))
    return jnp.concatenate(out).reshape(S, H * hd)


def _int8(a, axis):
    """``a`` rounded to 127 symmetric int8 steps of a scale per slice
    along ``axis``, in float32."""
    a = a.astype(jnp.float32)
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(a / s), -127, 127) * s


@partial(jax.jit, static_argnames=("conf_items", "dtype", "precision",
                                   "quant"))
def _stats(w, tokens, eval_tokens, *, conf_items, dtype, precision, quant):
    conf = dict(conf_items)
    d, eps = conf["hidden_size"], conf["rms_norm_eps"]
    H, K = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd, theta = conf["head_dim"], conf["rope_theta"]
    prec = jax.lax.Precision.HIGHEST if precision == "highest" else None
    c = lambda a: a.astype(dtype)
    if quant == "int8":
        mm = lambda x, wt: jnp.matmul(c(_int8(x, -1)), c(_int8(wt, 0)),
                                      precision=prec)
    else:
        mm = partial(jnp.matmul, precision=prec)
    S = tokens.shape[0]
    x = c(w["embed"][tokens] * math.sqrt(d))          # published embedding
    blk = w["stage0"]["b0"]
    for i in range(conf["num_hidden_layers"]):
        at, ml = blk["mixer"], blk["mlp"]
        h = _rms(x, c(1.0 + blk["ln1"][i]), eps)
        q = _rope(mm(h, c(at["wq"][i])).reshape(S, H, hd), theta)
        k = _rope(mm(h, c(at["wk"][i])).reshape(S, K, hd), theta)
        v = mm(h, c(at["wv"][i])).reshape(S, K, hd)
        x = x + mm(_attention(q, k, v, prec), c(at["wo"][i]))
        h = _rms(x, c(1.0 + blk["ln2"][i]), eps)
        x = x + mm(jax.nn.silu(mm(h, c(ml["wi_gate"][i])))
                   * mm(h, c(ml["wi_up"][i])), c(ml["wo"][i]))
    h = _rms(x, c(1.0 + w["final_norm"]), eps)
    head = c(w["head"])
    best, picked, at_eval = [], [], []
    for s0 in range(0, S, HEAD_BLOCK):
        lg = mm(h[s0:s0 + HEAD_BLOCK], head).astype(jnp.float32)
        best.append(lg.max(-1))
        picked.append(jnp.argmax(lg, -1).astype(jnp.int32))
        at_eval.append(jnp.take_along_axis(
            lg, eval_tokens[s0:s0 + HEAD_BLOCK, None], -1)[:, 0])
    return (jnp.concatenate(best), jnp.concatenate(picked),
            jnp.concatenate(at_eval))


def logit_stats(conf: dict, weights: dict, tokens, eval_tokens, *,
                dtype=jnp.float32, precision="highest", quant=None):
    """At each position of ``tokens`` (one padded sequence): the largest
    logit of the next token, the token it belongs to, and the logit of
    ``eval_tokens`` at that position."""
    keys = ("hidden_size", "rms_norm_eps", "num_attention_heads",
            "num_key_value_heads", "head_dim", "rope_theta",
            "num_hidden_layers")
    return _stats(weights, jnp.asarray(tokens, jnp.int32),
                  jnp.asarray(eval_tokens, jnp.int32),
                  conf_items=tuple((k, conf[k]) for k in keys),
                  dtype=dtype, precision=precision, quant=quant)
