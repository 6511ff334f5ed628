"""The plain reference of the Qwen3 dense decoder, and its weights.

Straightforward ``jax.numpy`` in float32 with matmul precision
``highest``: no kernels, no cache, no batching tricks, nothing imported
from the program. It follows the published block (Hugging Face
``Qwen3ForCausalLM``): pre-norm residual blocks, grouped-query attention
with a per-head RMS norm on q and k before rotate-half RoPE, a causal
softmax, SwiGLU.

Weights are the seed's: nine independent normal tensors from
``jax.random.split(jax.random.key(seed), 9)``, each scaled by
``fan_in ** -0.5`` (the embedding by 0.02) and rounded to the
configuration's dtype, all norm scales one. That recipe is part of the
yardstick: the served model has to hold the same numbers, and the
reference makes its own from the seed instead of taking the program's.
The output head is drawn on its own even where the published model ties
it to the embedding (departure: random tied weights would make every
token predict itself).

It runs after the window, layer by layer over the sampled requests, on
all the chips the cell has: weights are sharded over a 1-D mesh by
``jax.jit``'s own partitioner, activations replicated.

The contract a configuration's reference module keeps (its file names
the module under ``"reference"``; the harness calls these two and
nothing else, and hands ``weights`` from the first to the second
unopened):

``make_weights(config, seed, devices)``
    the seed's weights, made on ``devices`` in the dtype they are served
    in; ``config`` is the configuration file as a dict.
``judge(config, weights, samples, pad_to, rows_pad, *, block=None,
control=None, per_token=False)``
    the reference once over each ``(prompt, served tokens)`` pair of
    ``samples``, ``block`` pairs to a forward pass padded to ``pad_to``
    positions and ``rows_pad`` served tokens. Returns a dict with the
    numbers the cell's traffic file may limit (``logit_gap_max``,
    ``logit_gap_mean``) and ``tokens_compared``; with ``control`` the
    same numbers for the picks of the reference computed in that lower
    precision, under ``"control"``; with ``per_token`` every token's
    reading under ``"tokens"``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d: int
    ffn: int
    layers: int
    hq: int
    hkv: int
    hd: int
    theta: float
    eps: float
    dtype: str

    @classmethod
    def of(cls, config) -> "Dims":
        """From a configuration file's published keys (or the ``Dims``
        themselves)."""
        if isinstance(config, cls):
            return config
        return cls(
            vocab=config["vocab_size"], d=config["hidden_size"],
            ffn=config["intermediate_size"],
            layers=config["num_hidden_layers"],
            hq=config["num_attention_heads"],
            hkv=config["num_key_value_heads"],
            hd=config.get("head_dim") or (config["hidden_size"]
                                          // config["num_attention_heads"]),
            theta=float(config.get("rope_theta", 1e6)),
            eps=float(config.get("rms_norm_eps", 1e-6)),
            dtype={"bfloat16": "bfloat16", "float32": "float32"}[
                config.get("torch_dtype", "bfloat16")],
        )


# name -> (key index, shape function, scale or None, sharded axis)
def _layout(m: Dims) -> dict:
    L, d, F = m.layers, m.d, m.ffn
    return {
        "wq": (0, (L, d, m.hq * m.hd), None, 2),
        "wk": (1, (L, d, m.hkv * m.hd), None, 2),
        "wv": (2, (L, d, m.hkv * m.hd), None, 2),
        "gate": (3, (L, d, F), None, 2),
        "up": (4, (L, d, F), None, 2),
        "embed": (5, (m.vocab, d), 0.02, None),
        "wo": (6, (L, m.hq * m.hd, d), None, 1),
        "w2": (7, (L, F, d), None, 1),
        "lm_head": (8, (d, m.vocab), None, 1),
    }


def mesh_of(devices) -> Mesh:
    return Mesh(np.asarray(devices), ("r",))


def draw(layout: dict, n_keys: int, dtype: str, seed: int, devices) -> dict:
    """Tensors of ``layout`` (name -> key index, shape, scale or None for
    ``fan_in ** -0.5``, sharded axis or None) from ``n_keys`` splits of
    the seed's key, each made on the devices in one jitted call in
    ``dtype``."""
    mesh = mesh_of(devices)
    keys = jax.random.split(jax.random.key(seed), n_keys)
    out = {}
    for name, (ki, shape, scale, axis) in layout.items():
        if axis is not None and shape[axis] % len(devices):
            axis = None
        spec = [None] * len(shape)
        if axis is not None:
            spec[axis] = "r"
        s = scale if scale is not None else shape[-2] ** -0.5

        @functools.partial(jax.jit, static_argnums=(1, 2),
                           out_shardings=NamedSharding(mesh, P(*spec)))
        def rnd(k, shape, s):
            return (jax.random.normal(k, shape, jnp.float32) * s).astype(
                dtype)

        out[name] = rnd(keys[ki], shape, s)
    return out


def make_weights(config, seed: int, devices) -> dict:
    """The seed's weights, each made on the devices in one jitted call
    in the dtype they are served in."""
    m = Dims.of(config)
    return draw(_layout(m), 9, m.dtype, seed, devices)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """Rotate-half RoPE: x [B, S, H, hd], pos [S]."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _q8(x, axis):
    """Symmetric int8 codes and scale along ``axis`` (absmax / 127)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.rint(x / s), -127, 127).astype(jnp.int8), s


def _mm(x, w, mode: str):
    """``x @ w`` in float32 (``f32``). For the control (``int8``) both
    operands are rounded to int8, a scale per row of x and per column of
    w, and the products summed in int32. ``bf16`` rounds the input and
    the product to bfloat16 as the served model does: the tests' stand-in
    for a sound program, never the reference."""
    if mode == "bf16":
        y = jnp.dot(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
        return y.astype(jnp.bfloat16).astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "f32":
        return jnp.dot(x, w, precision="highest")
    xq, xs = _q8(x, -1)
    wq, ws = _q8(w, 0)
    acc = jnp.dot(xq, wq, preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


QUERY_BLOCK = 128


def _attend(m: Dims, q, k, v):
    """Causal softmax attention of ONE sequence, a block of query rows
    at a time so that the scores fit: q [S, hq, hd], k and v
    [S, hkv, hd] -> [S, hq, hd]."""
    S = q.shape[0]
    qb = min(QUERY_BLOCK, S)
    if S % qb:
        raise ValueError(f"sequence length {S} is no multiple of {qb}")
    g = m.hq // m.hkv
    blocks = q.reshape(S // qb, qb, m.hkv, g, m.hd)
    starts = jnp.arange(S // qb) * qb

    def block(args):
        qi, s0 = args
        s = jnp.einsum("skgd,tkd->kgst", qi, k, precision="highest")
        s = s * (m.hd ** -0.5)
        seen = (s0 + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgst,tkd->skgd", p, v, precision="highest")

    return jax.lax.map(block, (blocks, starts)).reshape(S, m.hq, m.hd)


def attention(m, mode: str, x, lw):
    """The attention half of a decoder layer over whole sequences, with
    its residual: x [B, S, d] float32. ``m`` gives hq, hkv, hd, eps and
    theta; ``lw`` the layer's wq, wk, wv and wo."""
    B, S, _ = x.shape
    h = _rms(x, m.eps)  # norm scales are all one
    q = _mm(h, lw["wq"], mode).reshape(B, S, m.hq, m.hd)
    k = _mm(h, lw["wk"], mode).reshape(B, S, m.hkv, m.hd)
    v = _mm(h, lw["wv"], mode).reshape(B, S, m.hkv, m.hd)
    pos = jnp.arange(S)
    q = _rope(_rms(q, m.eps), pos, m.theta)
    k = _rope(_rms(k, m.eps), pos, m.theta)
    if mode == "int8":  # the cache too: a scale per token and head
        kq, ks = _q8(k, -1)
        vq, vs = _q8(v, -1)
        k, v = kq.astype(jnp.float32) * ks, vq.astype(jnp.float32) * vs
    o = jax.lax.map(lambda qkv: _attend(m, *qkv), (q, k, v))
    return x + _mm(o.reshape(B, S, m.hq * m.hd), lw["wo"], mode)


def _layer(m: Dims, mode: str, x, lw):
    """One decoder layer over whole sequences: x [B, S, d] float32."""
    x = attention(m, mode, x, lw)
    h = _rms(x, m.eps)
    act = jax.nn.silu(_mm(h, lw["gate"], mode)) * _mm(h, lw["up"], mode)
    return x + _mm(act, lw["w2"], mode)


_LAYER_KEYS = ("wq", "wk", "wv", "wo", "gate", "up", "w2")


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer_at(m, mode, x, stacked, l):
    lw = {k: jax.lax.dynamic_index_in_dim(stacked[k], l, 0, keepdims=False)
          for k in _LAYER_KEYS}
    return _layer(m, mode, x, lw)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _head(m, mode, x, rows, cols, lm_head):
    """Logits [N, V] of the hidden rows ``x[rows, cols]``."""
    h = _rms(x[rows, cols], m.eps)
    return _mm(h, lm_head, mode)


@jax.jit
def _embed(embed, tokens):
    return jnp.take(embed, tokens, axis=0).astype(jnp.float32)


def forward_logits(m: Dims, weights: dict, tokens: np.ndarray,
                   rows: np.ndarray, cols: np.ndarray, *,
                   mode: str = "f32") -> jax.Array:
    """Full forward pass over ``tokens [B, S]`` (right-padded; causal
    attention keeps the padding out of every real position) and the
    logits at positions ``(rows[n], cols[n])``: [N, V] float32."""
    x = _embed(weights["embed"], jnp.asarray(tokens, jnp.int32))
    stacked = {k: weights[k] for k in _LAYER_KEYS}
    for l in range(m.layers):
        x = _layer_at(m, mode, x, stacked, jnp.int32(l))
    return _head(m, mode, x, jnp.asarray(rows, jnp.int32),
                 jnp.asarray(cols, jnp.int32), weights["lm_head"])


@jax.jit
def _gaps(logits, served):
    """Per position: how far the served token's logit lies below the
    best, the best token, the best's lead over the second best, and the
    largest |logit|."""
    top2 = jax.lax.top_k(logits, 2)[0]
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    return (top2[:, 0] - got, jnp.argmax(logits, axis=-1),
            top2[:, 0] - top2[:, 1], jnp.max(jnp.abs(logits)))


def _judge_block(forward, samples, n_seqs, pad_to, rows_pad, control):
    """One forward pass over at most ``n_seqs`` sampled requests, padded
    to fixed shapes. Per served token: its gap, whether it is the
    reference's best, the reference's lead; with ``control`` also the gap
    of the token the lower precision puts first there."""
    tokens = np.zeros((n_seqs, pad_to), np.int32)
    rows, cols, served = [], [], []
    for b, (prompt, out) in enumerate(samples):
        seq = list(prompt) + list(out)
        if len(seq) > pad_to:
            raise ValueError(f"sample of {len(seq)} tokens > pad {pad_to}")
        tokens[b, : len(seq)] = seq
        for j, t in enumerate(out):
            rows.append(b)
            cols.append(len(prompt) - 1 + j)
            served.append(t)
    n = len(served)
    if n > rows_pad:
        raise ValueError(f"{n} served tokens > rows_pad {rows_pad}")
    pad = rows_pad - n
    rows_a = np.asarray(rows + [0] * pad, np.int32)
    cols_a = np.asarray(cols + [0] * pad, np.int32)
    served_a = jnp.asarray(served + [0] * pad, jnp.int32)
    logits = forward(tokens, rows_a, cols_a, "f32")
    gap, best, lead, top = _gaps(logits, served_a)
    out = {"gap": np.asarray(gap)[:n],
           "is_best": np.asarray(best)[:n] == np.asarray(served),
           "lead": np.asarray(lead)[:n], "row": np.asarray(rows),
           "col": np.asarray(cols), "absmax": float(top)}
    if control:
        low = forward(tokens, rows_a, cols_a, control)
        low_best = jnp.argmax(low, axis=-1)
        out["control_gap"] = np.asarray(_gaps(logits, low_best)[0])[:n]
    return out


def readings(gap: np.ndarray) -> dict:
    """The numbers compared, from every served token's gap: the widest,
    the mean, and the share of tokens that are not the reference's best
    (a gap of nought is the reference's best or its equal)."""
    return {"logit_gap_max": float(gap.max()),
            "logit_gap_mean": float(gap.mean()),
            "not_best_share": float((gap > 0).mean())}


def judge(config, weights: dict, samples: list, pad_to: int,
          rows_pad: int, **kw) -> dict:
    """``judge_with`` this module's forward pass over ``weights``."""
    m = Dims.of(config)
    return judge_with(
        lambda tokens, rows, cols, mode: forward_logits(
            m, weights, tokens, rows, cols, mode=mode),
        samples, pad_to, rows_pad, **kw)


def judge_with(forward, samples: list, pad_to: int, rows_pad: int, *,
               block: int | None = None, control: str | None = None,
               per_token: bool = False) -> dict:
    """Run the reference once over each sampled prompt with its served
    tokens, ``block`` requests to a forward pass (all in one where None;
    ``rows_pad`` is a block's room for served tokens). ``samples`` is a
    list of ``(prompt, served)``; ``forward(tokens [B, S], rows [N],
    cols [N], mode)`` gives the logits [N, V] at positions ``(rows[n],
    cols[n])`` in precision ``mode`` (``f32``, or the control's): the
    one thing an architecture's reference brings to the comparison.
    Returns the widest and mean gap by
    which a served token's logit lies below the reference's best and the
    share of served tokens that are not its best; with ``control``
    (``int8``) also those of the tokens which the reference in that
    precision puts first at the same positions, under ``control``; with
    ``per_token`` every token's reading, under ``tokens``."""
    block = block or len(samples)
    parts = [_judge_block(forward, samples[i: i + block], block, pad_to,
                          rows_pad, control)
             for i in range(0, len(samples), block)]
    gap = np.concatenate([p["gap"] for p in parts])
    out = {"tokens_compared": int(gap.size),
           "requests_compared": len(samples), **readings(gap),
           "reference_logit_absmax": max(p["absmax"] for p in parts)}
    if control:
        out["control"] = readings(
            np.concatenate([p["control_gap"] for p in parts]))
    if per_token:
        out["tokens"] = {
            "request": np.concatenate(
                [p["row"] + i * block for i, p in enumerate(parts)]).tolist(),
            "position": np.concatenate([p["col"] for p in parts]).tolist(),
            **{k: np.concatenate([p[k] for p in parts]).tolist()
               for k in ("gap", "lead")
               + (("control_gap",) if control else ())}}
    return out
