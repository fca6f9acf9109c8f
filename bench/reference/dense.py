"""Plain float32 reference of a dense decoder with grouped-query
attention, rotary positions over the whole head, RMSNorm and a SwiGLU
MLP, in straightforward ``jax.numpy`` at the highest matmul precision.

It imports nothing of the program.  It reads the weights the benchmark
made, by the program's parameter names (``embed``, ``layers/sub_0/...``
stacked over layers, ``final_norm``, ``lm_head``), and runs one layer
per call so that it fits beside them.  Queries are walked in blocks of
``QB`` so that the scores of a long sequence fit too.

The top-level keys of a configuration file describe the published
model; its ``departures`` group overrides them with what the program
runs instead (on stablelm-2: RMSNorm for LayerNorm, rotary over the
whole head, no q/k/v bias), and the reference runs the same.  The
group's ``prompt_padding`` states how the served path pads a prompt
before its first token (``context``), so that a change of that
convention in the program is a change of a data file here.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QB = 256                                  # query block, and length grid


def as_run(cfg: Dict) -> Dict:
    """The configuration as it is run: the published keys with the
    ``departures`` group laid over them."""
    departures = cfg.get("departures", {})
    if not isinstance(departures, dict):
        raise ValueError("'departures' must map keys to the values run")
    return {**cfg, **departures}


def check(cfg: Dict) -> Dict:
    run = as_run(cfg)
    if run.get("norm", "rmsnorm") != "rmsnorm":
        raise ValueError("dense reference normalises by RMSNorm")
    if run.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError("dense reference rotates the whole head")
    if run.get("use_qkv_bias", False):
        raise ValueError("dense reference has no q/k/v bias")
    if run.get("hidden_act", "silu") != "silu":
        raise ValueError("dense reference is SwiGLU")
    return run


def context(cfg: Dict, pool: Dict, prompt: np.ndarray) -> np.ndarray:
    """The tokens the first served token follows, by the configuration's
    ``prompt_padding``: ``{"token", "attended", "to"}``.  ``to`` names
    the pool's bucket key and then its chunk key: a prompt that fits the
    bucket is left-padded to it, a longer one to the next multiple of
    the chunk (the bucket where the chunk is unset).  Pads that are not
    attended change nothing under rotary positions, so they are left
    out.  No ``prompt_padding``: the prompt as sent."""
    pad = as_run(cfg).get("prompt_padding")
    n = len(prompt)
    if not pad or not pad["attended"]:
        return np.asarray(prompt, np.int32)
    bucket_key, chunk_key = pad["to"]
    bucket = int(pool[bucket_key])
    chunk = int(pool.get(chunk_key) or bucket)
    width = bucket if n <= bucket else -(-n // chunk) * chunk
    return np.concatenate([np.full(width - n, pad["token"], np.int32),
                           np.asarray(prompt, np.int32)])


def program_kwargs(cfg: Dict) -> Dict:
    """The sizes under the program's ``ModelConfig`` field names."""
    cfg = check(cfg)
    heads = cfg["num_attention_heads"]
    return dict(name=cfg["name"], family="dense",
                num_layers=cfg["num_hidden_layers"],
                d_model=cfg["hidden_size"], num_heads=heads,
                num_kv_heads=cfg["num_key_value_heads"],
                d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
                head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
                rope_theta=float(cfg["rope_theta"]), norm_eps=_eps(cfg),
                tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
                remat=False)


def _eps(cfg: Dict) -> float:
    return float(as_run(cfg)["rms_norm_eps"])


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: [S, n, hd]; rotate-half over the whole head."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _int8(x, axis):
    """Symmetric absmax int8 round trip along ``axis``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                        1e-8) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _qmm(a, b):
    """The control's matmul: activations per row and weights per output
    column through int8, then the same product."""
    return _mm(_int8(a, -1), _int8(b, 0))


def _attention(q, k, v):
    """Causal attention.  q: [S, KV, g, hd]; k, v: [S, KV, hd]."""
    s, kv, g, hd = q.shape
    kpos = jnp.arange(s)

    def block(qb_and_start):
        qb, start = qb_and_start
        sc = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HI)
        sc = sc / math.sqrt(hd)
        qpos = start + jnp.arange(qb.shape[0])
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", w, v, precision=HI)

    blocks = q.reshape(s // QB, QB, kv, g, hd)
    starts = jnp.arange(s // QB) * QB
    return jax.lax.map(block, (blocks, starts)).reshape(s, kv, g, hd)


@partial(jax.jit, static_argnames=("dims", "int8"))
def _layer(layers, i, x, dims, int8=False):
    heads, kv, hd, theta, eps = dims
    mm = _qmm if int8 else _mm
    p = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
        layers["sub_0"])
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms(x, p["norm1"]["scale"], eps)
    m = p["mixer"]
    f32 = lambda w: w.astype(jnp.float32)
    q = _rope(mm(h, f32(m["wq"])).reshape(s, heads, hd), pos, theta)
    k = _rope(mm(h, f32(m["wk"])).reshape(s, kv, hd), pos, theta)
    v = mm(h, f32(m["wv"])).reshape(s, kv, hd)
    o = _attention(q.reshape(s, kv, heads // kv, hd), k, v)
    x = x + mm(o.reshape(s, heads * hd), f32(m["wo"]))
    h = _rms(x, p["norm2"]["scale"], eps)
    f = p["mlp"]
    act = jax.nn.silu(mm(h, f32(f["w_gate"]))) * mm(h, f32(f["w_in"]))
    return x + mm(act, f32(f["w_out"]))


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps",))
def _score(x, rows, served, final_scale, head, eps):
    """Gap of each served token below the row's best logit, in units of
    the row's standard deviation, and the row's argmax."""
    h = _rms(x[rows], final_scale, eps)
    logits = _mm(h, head.astype(jnp.float32).T)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return (best - got) / jnp.std(logits, axis=-1), jnp.argmax(logits, -1)


@partial(jax.jit, static_argnames=("eps",))
def _int8_choice(x, rows, final_scale, head, eps):
    """The token the int8 control puts first at each row."""
    h = _rms(x[rows], final_scale, eps)
    return jnp.argmax(_qmm(h, head.astype(jnp.float32).T), axis=-1)


def _dims(cfg: Dict):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // heads
    return heads, kv, hd, float(cfg["rope_theta"]), _eps(cfg)


def hidden(params, cfg: Dict, seq: np.ndarray, int8: bool = False):
    """The last layer's output [S', D] for ``seq``, S' the length rounded
    up to the ``QB`` grid (tail pads are causal, so never read).
    ``int8``: every projection through int8 (the control)."""
    cfg = check(cfg)
    padded = np.zeros(-(-len(seq) // QB) * QB, np.int32)
    padded[:len(seq)] = seq
    x = _embed(params["embed"], jnp.asarray(padded))
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(params["layers"], jnp.int32(i), x, _dims(cfg), int8)
    return x


def _head(params, cfg: Dict):
    return (params["embed"] if cfg.get("tie_word_embeddings")
            else params["lm_head"])


def logits(params, cfg: Dict, seq: np.ndarray) -> np.ndarray:
    """Float32 logits [len(seq), V] at every position (small sizes)."""
    h = _rms(hidden(params, cfg, seq)[:len(seq)],
             params["final_norm"]["scale"], _eps(cfg))
    return np.asarray(_mm(h, _head(params, cfg).astype(jnp.float32).T))


def score(params, cfg: Dict, seq: np.ndarray, first: int,
          served: np.ndarray, control: bool = False
          ) -> Tuple[np.ndarray, np.ndarray]:
    """``seq``: the padded prompt followed by the served tokens but the
    last; ``served[j]`` was produced from position ``first + j``.
    Returns (gap [n], reference argmax [n]).  ``control``: score, in
    place of the served tokens, the token that the reference computed
    through int8 puts first at each of those positions."""
    x = hidden(params, cfg, seq)
    m = len(served)                   # rows padded to the grid too, so
    rows = np.zeros(-(-m // QB) * QB, np.int32)   # one program serves
    toks = np.zeros_like(rows)                     # every length
    rows[:m] = first + np.arange(m)
    toks[:m] = served
    if control:
        toks = np.asarray(_int8_choice(
            hidden(params, cfg, seq, int8=True), jnp.asarray(rows),
            params["final_norm"]["scale"], _head(params, cfg), _eps(cfg)))
    gap, best = _score(x, jnp.asarray(rows), jnp.asarray(toks),
                       params["final_norm"]["scale"], _head(params, cfg),
                       _eps(cfg))
    return np.asarray(gap)[:m], np.asarray(best)[:m]
