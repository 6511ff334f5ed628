"""The plain reference of one rank's share of the DeepSeek-V3 language
block (``rednote-hilab/dots.vlm1.inst``'s language model), and its
weights.

Straightforward ``jax.numpy`` in float32 at matmul precision
``highest``: no kernels, no cache, nothing imported from the program.
Every norm is RMSNorm (eps from the file, scales all one). Per token,
``x`` the residual stream:

*Latent attention, every layer (the EXPANDED form; the program decodes
in the absorbed one).* ``c_q = norm(x W_qa)``; ``q = c_q W_qb``, heads of
``[q_nope; q_rope]``. ``[c_kv; k_r] = x W_kva``; ``c_kv = norm(c_kv)``;
``k_rope = RoPE(k_r)``, one row for all heads; ``q_rope = RoPE(q_rope)``.
``k_nope_h = c_kv W_kb_h``, ``v_h = c_kv W_vb_h`` (the published
``kv_b_proj``'s key and value columns, drawn as two tensors);
``score_h = s (q_nope_h . k_nope_h + q_rope_h . k_rope)``, causal
softmax, ``o_h = P v_h``, ``out = concat_h(o_h) W_o``. RoPE is YaRN: per
frequency a blend of ``theta^(-2i/d)`` and that over ``factor``, a
linear ramp between the correction dims of ``beta_fast`` and
``beta_slow`` turns over the original length; cos and sin are scaled by
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` (1 as
published) and ``s = (nope + rope)^-0.5 mscale(factor,
mscale_all_dim)^2`` with ``mscale(f, m) = 0.1 m ln f + 1``. **The rotary
dims rotate in halves** (dim i pairs with i + rope/2), the program's
convention; the published code de-interleaves them first, which for
seeded random weights is a permutation of columns of ``W_qb`` and
``W_kva``.

*Feed-forward.* The ``first_k_dense_replace`` leading layers: SwiGLU of
``intermediate_size``. Every other layer: ``s = sigmoid(x W_g)`` over
ALL the router's experts (``published.n_routed_experts``); the choice
is made on ``s + b`` (``e_score_correction_bias``): a group's score is
the sum of its two largest, the best ``topk_group`` of ``n_group`` groups
are kept, the ``num_experts_per_tok`` best inside them chosen; weights
are the chosen experts' ``s``, divided by their sum, times
``routed_scaling_factor``. This rank holds experts ``[expert_offset,
expert_offset + n_routed_experts)``: ``y = sum over chosen AND held of
w_e FFN_e(x) + FFN_shared(x)``, the weights normalised over all the
chosen, held or not. Every held expert runs on the rows that chose it,
gathered at a fixed capacity of four times their expected number (an
expert that more rows chose runs on every row instead, weighted by the
gate, nought where not chosen: nothing is dropped).

Weights are the seed's, drawn ONE MATRIX AT A TIME: the tensors of
:func:`_layout` in order, each split over its leading axes (layer,
expert) into its ``[rows, cols]`` matrices, and matrix ``j`` of them all
takes key ``j`` of ``jax.random.split(jax.random.key(seed), total)``: a
normal matrix scaled by ``rows ** -0.5`` (embedding and the bias, a
vector a layer, by 0.02) and rounded to the configuration's dtype (the
bias stays float32); gate and up are one fused matrix ``[d, 2 f]``, gate
first. The served model has to hold the same numbers. (Not
``benchmark.reference.draw``: that makes a whole stacked tensor's
float32 normals at once, 15 GB for the held experts' gate|up.)

It keeps the contract ``benchmark/reference.py`` states, reuses that
module's ``judge_with`` and small helpers, and is named by
``benchmark/configs/dots-vlm1-ep16.json`` (and the tests'
``tiny-mla-moe.config.json``) alone. A forward pass is cut to the
sampled requests' own length (rounded up to :data:`LENGTH_GRAIN`);
attention runs a block of query rows at a time, and the held experts one
after the other (a ``lax.map`` and a ``lax.scan``: one small program a
layer kind and length).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark import reference as dense

QUERY_BLOCK = 256
LENGTH_GRAIN = 512


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d: int
    ffn: int          # the leading dense layers' width
    dense_layers: int
    expert_layers: int
    heads: int
    nope: int
    rope: int
    v: int
    q_rank: int
    kv_rank: int
    experts: int      # the router's width
    held: int
    offset: int
    top_k: int
    n_group: int
    topk_group: int
    route_scale: float
    expert_ffn: int
    shared_ffn: int
    theta: float
    yarn: tuple       # (factor, beta_fast, beta_slow, original, mscale, all_dim) or ()
    eps: float
    dtype: str

    @classmethod
    def of(cls, c: dict) -> "Dims":
        if isinstance(c, cls):
            return c
        ld = c["first_k_dense_replace"]
        rs = c.get("rope_scaling") or {}
        yarn = ()
        if rs:
            yarn = (float(rs["factor"]), float(rs["beta_fast"]),
                    float(rs["beta_slow"]),
                    int(rs["original_max_position_embeddings"]),
                    float(rs["mscale"]), float(rs["mscale_all_dim"]))
        return cls(
            vocab=c["vocab_size"], d=c["hidden_size"],
            ffn=c["intermediate_size"], dense_layers=ld,
            expert_layers=c["num_hidden_layers"] - ld,
            heads=c["num_attention_heads"], nope=c["qk_nope_head_dim"],
            rope=c["qk_rope_head_dim"], v=c["v_head_dim"],
            q_rank=c["q_lora_rank"], kv_rank=c["kv_lora_rank"],
            experts=(c.get("published") or {}).get(
                "n_routed_experts", c["n_routed_experts"]),
            held=c["n_routed_experts"],
            offset=int(c.get("expert_offset", 0)),
            top_k=c["num_experts_per_tok"], n_group=c["n_group"],
            topk_group=c["topk_group"],
            route_scale=float(c["routed_scaling_factor"]),
            expert_ffn=c["moe_intermediate_size"],
            shared_ffn=c["moe_intermediate_size"] * c["n_shared_experts"],
            theta=float(c["rope_theta"]), yarn=yarn,
            eps=float(c.get("rms_norm_eps", 1e-6)),
            dtype=c.get("torch_dtype", "bfloat16"))


def _layout(m: Dims) -> list:
    """``(name, leading axes, matrix shape, scale or None)``, in the
    order the keys are handed out."""
    d, h = m.d, m.heads

    def attn(g, n):
        return [
            (f"{g}.wq_a", (n,), (d, m.q_rank), None),
            (f"{g}.wq_b", (n,), (m.q_rank, h * (m.nope + m.rope)), None),
            (f"{g}.wkv_a", (n,), (d, m.kv_rank + m.rope), None),
            (f"{g}.wk_b", (n,), (m.kv_rank, h * m.nope), None),
            (f"{g}.wv_b", (n,), (m.kv_rank, h * m.v), None),
            (f"{g}.wo", (n,), (h * m.v, d), None),
        ]

    n = m.expert_layers
    return [
        *attn("d", m.dense_layers),
        ("d.w1", (m.dense_layers,), (d, 2 * m.ffn), None),
        ("d.w2", (m.dense_layers,), (m.ffn, d), None),
        *attn("s", n),
        ("s.router", (n,), (d, m.experts), None),
        ("s.bias", (n,), (m.experts,), 0.02),
        ("s.w1", (n, m.held), (d, 2 * m.expert_ffn), None),
        ("s.w2", (n, m.held), (m.expert_ffn, d), None),
        ("s.shared_w1", (n,), (d, 2 * m.shared_ffn), None),
        ("s.shared_w2", (n,), (m.shared_ffn, d), None),
        ("embed", (), (m.vocab, d), 0.02),
        ("lm_head", (), (d, m.vocab), None),
    ]


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(keys, lead, mat, scale, dtype):
    def one(k):
        return (jax.random.normal(k, mat, jnp.float32) * scale).astype(dtype)

    return jax.lax.map(one, keys).reshape(*lead, *mat)


def make_weights(config, seed: int, devices) -> dict:
    m = Dims.of(config)
    layout = _layout(m)
    total = sum(math.prod(lead) for _, lead, _, _ in layout)
    keys = jax.device_put(
        jax.random.split(jax.random.key(seed), total),
        NamedSharding(dense.mesh_of(devices), P()))
    w, at = {}, 0
    for name, lead, mat, scale in layout:
        n = math.prod(lead)
        w[name] = _draw(keys[at: at + n], lead, mat,
                        scale if scale is not None else mat[-2] ** -0.5,
                        "float32" if name == "s.bias" else m.dtype)
        at += n
    return w


# -- rotary ---------------------------------------------------------------

def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def inv_freq(m: Dims) -> np.ndarray:
    base = m.theta ** -(np.arange(0, m.rope, 2, dtype=np.float64) / m.rope)
    if not m.yarn:
        return base.astype(np.float32)
    factor, fast, slow, original = m.yarn[:4]

    def correction_dim(turns):
        return (m.rope * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(m.theta)))

    low = max(math.floor(correction_dim(fast)), 0)
    high = min(math.ceil(correction_dim(slow)), m.rope - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(m.rope // 2) - low) / (high - low), 0, 1)
    return (base / factor * ramp + base * (1 - ramp)).astype(np.float32)


def softmax_scale(m: Dims) -> float:
    s = (m.nope + m.rope) ** -0.5
    if m.yarn and m.yarn[5]:
        s *= _mscale(m.yarn[0], m.yarn[5]) ** 2
    return s


def _rope(m: Dims, x, pos):
    """Rotate-half over the last axis: x [..., S, rope] with pos [S]
    broadcast from the axis before last."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq(m))
    scale = (_mscale(m.yarn[0], m.yarn[4]) / _mscale(m.yarn[0], m.yarn[5])
             if m.yarn else 1.0)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- layers ---------------------------------------------------------------

def _attend(m: Dims, q_nope, q_rope, k_nope, k_rope, v):
    """Causal attention of ONE sequence, a block of query rows at a time
    so that the scores fit: q_* [S, H, .], k_nope [S, H, nope], k_rope
    [S, rope], v [S, H, v] -> [S, H, v]."""
    S = q_nope.shape[0]
    qb = math.gcd(S, QUERY_BLOCK)
    blocks = (q_nope.reshape(S // qb, qb, m.heads, m.nope),
              q_rope.reshape(S // qb, qb, m.heads, m.rope),
              jnp.arange(S // qb) * qb)

    def block(args):
        qn, qr, s0 = args
        s = (jnp.einsum("qhn,khn->hqk", qn, k_nope, precision="highest")
             + jnp.einsum("qhr,kr->hqk", qr, k_rope, precision="highest")
             ) * softmax_scale(m)
        seen = (s0 + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khv->qhv", p, v, precision="highest")

    return jax.lax.map(block, blocks).reshape(S, m.heads, m.v)


def attention(m: Dims, mode: str, x, lw):
    """The latent attention half of a layer with its residual: x [B, S,
    d] float32; ``lw`` the layer's wq_a .. wo."""
    B, S, _ = x.shape
    h = dense._rms(x, m.eps)
    c_q = dense._rms(dense._mm(h, lw["wq_a"], mode), m.eps)
    q = dense._mm(c_q, lw["wq_b"], mode).reshape(B, S, m.heads,
                                                 m.nope + m.rope)
    kv = dense._mm(h, lw["wkv_a"], mode)
    c_kv = dense._rms(kv[..., : m.kv_rank], m.eps)
    pos = jnp.arange(S)
    k_rope = _rope(m, kv[..., m.kv_rank:], pos)
    q_rope = _rope(m, q[..., m.nope:].swapaxes(1, 2), pos).swapaxes(1, 2)
    if mode == "int8":  # the cache row too: a scale per token and part
        cq, cs = dense._q8(c_kv, -1)
        rq, rs = dense._q8(k_rope, -1)
        c_kv, k_rope = cq.astype(jnp.float32) * cs, rq.astype(jnp.float32) * rs
    k_nope = dense._mm(c_kv, lw["wk_b"], mode).reshape(B, S, m.heads, m.nope)
    v = dense._mm(c_kv, lw["wv_b"], mode).reshape(B, S, m.heads, m.v)
    o = jnp.stack([
        _attend(m, q[b, ..., : m.nope], q_rope[b], k_nope[b], k_rope[b], v[b])
        for b in range(B)])
    return x + dense._mm(o.reshape(B, S, m.heads * m.v), lw["wo"], mode)


def _swiglu(h, w1, w2, mode):
    gate, up = jnp.split(dense._mm(h, w1, mode), 2, axis=-1)
    return dense._mm(jax.nn.silu(gate) * up, w2, mode)


def route(m: Dims, h, router, bias):
    """Chosen experts ``[T, k]`` and their weights for rows h [T, d]."""
    s = jax.nn.sigmoid(dense._mm(h, router, "f32"))
    t = s.shape[0]
    choice = (s + bias).reshape(t, m.n_group, -1)
    group = jnp.sum(jax.lax.top_k(choice, 2)[0], axis=-1)
    kept = jax.nn.one_hot(jax.lax.top_k(group, m.topk_group)[1],
                          m.n_group).sum(axis=-2) > 0
    choice = jnp.where(kept[:, :, None], choice, -jnp.inf).reshape(t, -1)
    ids = jax.lax.top_k(choice, m.top_k)[1]
    w = jnp.take_along_axis(s, ids, axis=-1)
    return ids, w / jnp.sum(w, axis=-1, keepdims=True) * m.route_scale


def experts(m: Dims, mode: str, x, lw, live):
    """The share's expert layer with its residual: x [B, S, d]; rows
    ``live [B, S]`` marks are routed (the rest is padding)."""
    B, S, d = x.shape
    h = dense._rms(x, m.eps).reshape(B * S, d)
    ids, w = route(m, h, lw["router"], lw["bias"])
    t = B * S
    cap = min(t, max(8, -(-4 * t * m.top_k // m.experts)))
    y = _swiglu(h, lw["shared_w1"], lw["shared_w2"], mode)
    live = live.reshape(t)

    def expert(y, xs):
        e, w1, w2 = xs
        gate = jnp.sum(jnp.where(ids == m.offset + e, w, 0.0), axis=-1)
        chose = (gate > 0) & live

        def gathered(y):
            rows = jnp.nonzero(chose, size=cap, fill_value=t)[0]
            safe = jnp.minimum(rows, t - 1)
            part = _swiglu(h[safe], w1, w2, mode) * gate[safe][:, None]
            return y.at[rows].add(part, mode="drop")

        def every_row(y):
            return y + _swiglu(h, w1, w2, mode) * gate[:, None]

        return jax.lax.cond(jnp.sum(chose) <= cap, gathered, every_row,
                            y), None

    y, _ = jax.lax.scan(
        expert, y, (jnp.arange(m.held), lw["w1"], lw["w2"]))
    return x + y.reshape(B, S, d)


_ATTN = ("wq_a", "wq_b", "wkv_a", "wk_b", "wv_b", "wo")
_DENSE = _ATTN + ("w1", "w2")
_SPARSE = _ATTN + ("router", "bias", "w1", "w2", "shared_w1", "shared_w2")


def _at(stacked, keys, l):
    return {k: jax.lax.dynamic_index_in_dim(stacked[k], l, 0, keepdims=False)
            for k in keys}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _dense_layer_at(m, mode, x, stacked, l):
    lw = _at(stacked, _DENSE, l)
    x = attention(m, mode, x, lw)
    return x + _swiglu(dense._rms(x, m.eps), lw["w1"], lw["w2"], mode)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _sparse_layer_at(m, mode, x, stacked, l, live):
    lw = _at(stacked, _SPARSE, l)
    return experts(m, mode, attention(m, mode, x, lw), lw, live)


def forward_logits(m: Dims, weights: dict, tokens, rows, cols, *,
                   mode: str = "f32") -> jax.Array:
    """Full forward pass over ``tokens [B, S]`` (right-padded) and the
    logits at ``(rows[n], cols[n])``: [N, V] float32. Positions past a
    row's last asked-for column are padding: cut off where no row needs
    them, kept out of the routing otherwise."""
    tokens, rows, cols = (np.asarray(a) for a in (tokens, rows, cols))
    last = np.zeros(tokens.shape[0], np.int64)
    np.maximum.at(last, rows, cols)
    S = min(tokens.shape[1], -(-(int(last.max()) + 1) // LENGTH_GRAIN)
            * LENGTH_GRAIN)
    live = jnp.asarray(np.arange(S)[None, :] <= last[:, None])
    x = dense._embed(weights["embed"], jnp.asarray(tokens[:, :S], jnp.int32))
    for g, n, layer_at, extra in (
            ("d.", m.dense_layers, _dense_layer_at, ()),
            ("s.", m.expert_layers, _sparse_layer_at, (live,))):
        stacked = {k[2:]: v for k, v in weights.items() if k.startswith(g)}
        for l in range(n):
            x = layer_at(m, mode, x, stacked, jnp.int32(l), *extra)
    return dense._head(m, mode, x, jnp.asarray(rows, jnp.int32),
                       jnp.asarray(cols, jnp.int32), weights["lm_head"])


def judge(config, weights: dict, samples: list, pad_to: int, rows_pad: int,
          **kw) -> dict:
    m = Dims.of(config)
    return dense.judge_with(
        lambda tokens, rows, cols, mode: forward_logits(
            m, weights, tokens, rows, cols, mode=mode),
        samples, pad_to, rows_pad, **kw)
