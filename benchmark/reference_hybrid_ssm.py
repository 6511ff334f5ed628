"""The plain reference of a hybrid of Mamba-2 and GQA attention layers
(``ibm-granite/granite-4.0-h-micro``; Hugging Face
``GraniteMoeHybridForCausalLM`` with no routed experts), and its
weights.

Straightforward ``jax.numpy`` in float32 at matmul precision
``highest``: no kernels, no cache, no chunks, nothing imported from the
program. Every norm is RMSNorm (eps from the file, scales all one). With
``x`` the residual stream and ``r`` the ``residual_multiplier``:

*Block.* ``x += r mixer(rms(x))``, then ``x += r mlp(rms(x))``, ``mlp(v)
= (silu(v W_gate) * (v W_up)) W_down`` in every layer; the input is
``embed[id] x embedding_multiplier``, the output ``rms(x) W_head /
logits_scaling``.

*``mamba`` mixer* (``u`` its input; ``di = H x P`` channels in ``H``
heads of ``P``; ``N`` the state a channel; one group). ``[z | xBC | dt]
= u W_in`` split ``di | di + 2 N | H``. A causal depthwise convolution
of ``mamba_d_conv`` taps with bias over ``xBC`` (zeros before the
start), then silu; ``[x | B | C] = xBC``, ``B`` and ``C`` shared by all
heads. ``delta = softplus(dt + dt_bias) [H]``, ``A = -exp(A_log) [H]``.
**The recurrence runs ONE POSITION AT A TIME under ``lax.scan``: ``S_t =
exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t``, ``y_t = S_t C_t + D
x_t``, ``S [H, P, N]`` from zeros** (the program prefills in the
chunked state-space-duality form and decodes through a kernel: the two
forms check each other). Then ``g = y * silu(z)``, ``g / sqrt(mean(g^2)
+ eps)`` over all ``di``, and ``g W_out``.

*``attention`` mixer.* ``num_attention_heads`` query and
``num_key_value_heads`` key/value heads of ``hidden_size /
num_attention_heads``, no bias, no q/k norm, NO positions
(``position_embedding_type`` ``nope``), ``softmax(q k^T x
attention_multiplier)``, causal.

Weights are the seed's, ONE MATRIX A KEY: the tensors of
:func:`_layout` in order, each split over its leading (layer) axis,
matrix ``j`` of them all from key ``j`` of
``jax.random.split(jax.random.key(seed), total)``: normal times ``rows
** -0.5`` (the embedding 0.02; the convolution ``[taps, channels]``
therefore ``taps ** -0.5``) rounded to the configuration's dtype; q, k
and v are one matrix ``[d, (hq + 2 hkv) hd]``, gate and up one ``[d, 2
f]``. What is no matrix takes the published initial ranges in float32:
``A`` uniform in ``[1, 16)``, ``dt_bias`` the inverse softplus of a step
log-uniform in ``[1e-3, 1e-1)``, ``D`` one, the convolution's bias
nought. The served model has to hold the same numbers.

**Departures from the published model:** seeded random weights (no
checkpoint is on the machine), and the output head drawn on its own
though ``tie_word_embeddings`` is true (random tied weights would make
every token predict itself: ``benchmark/reference.py`` states the same).

It keeps the contract ``benchmark/reference.py`` states and reuses that
module's ``judge_with``, ``_mm`` (so the ``int8`` control is the same
control), ``_rms``, ``_embed`` and ``_head``. A control of its own:
``bf16_state`` is this reference with ``S`` rounded to bfloat16 after
every position and everything else float32: what a bf16 state would
cost. A forward pass is cut to the sampled requests' own length (rounded
up to :data:`LENGTH_GRAIN`).
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
LENGTH_GRAIN = 256
DT_MIN, DT_MAX = 1e-3, 1e-1
A_MIN, A_MAX = 1.0, 16.0


@dataclasses.dataclass(frozen=True)
class Dims:
    vocab: int
    d: int
    ffn: int
    kinds: tuple      # "mamba" | "attention", a layer
    hq: int
    hkv: int
    hd: int
    attn_scale: float
    embed_mult: float
    res_mult: float
    logit_div: float
    heads: int        # the mixer's H
    head_dim: int     # P
    state: int        # N
    taps: int
    eps: float
    dtype: str

    @classmethod
    def of(cls, c) -> "Dims":
        if isinstance(c, cls):
            return c
        if c.get("mamba_n_groups", 1) != 1:
            raise ValueError("one group of B / C only")
        if len(c["layer_types"]) != c["num_hidden_layers"]:
            raise ValueError("layer_types names another number of layers")
        hd = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
        return cls(
            vocab=c["vocab_size"], d=c["hidden_size"],
            ffn=c.get("shared_intermediate_size") or c["intermediate_size"],
            kinds=tuple(c["layer_types"]),
            hq=c["num_attention_heads"], hkv=c["num_key_value_heads"], hd=hd,
            attn_scale=float(c.get("attention_multiplier") or hd ** -0.5),
            embed_mult=float(c.get("embedding_multiplier", 1.0)),
            res_mult=float(c.get("residual_multiplier", 1.0)),
            logit_div=float(c.get("logits_scaling", 1.0)),
            heads=c["mamba_n_heads"], head_dim=c["mamba_d_head"],
            state=c["mamba_d_state"], taps=c["mamba_d_conv"],
            eps=float(c.get("rms_norm_eps", 1e-5)),
            dtype=c.get("torch_dtype", "bfloat16"))

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.state


def _layout(m: Dims) -> list:
    """``(name, layers, shape, scale or kind)`` in the order the keys
    are handed out; ``"a"`` and ``"dt"`` are the two float32 vectors."""
    lm = sum(k == "mamba" for k in m.kinds)
    la = len(m.kinds) - lm
    return [
        ("m.w_in", lm, (m.d, m.inner + m.conv_dim + m.heads), None),
        ("m.conv_w", lm, (m.taps, m.conv_dim), None),
        ("m.a_log", lm, (m.heads,), "a"),
        ("m.dt_bias", lm, (m.heads,), "dt"),
        ("m.w_out", lm, (m.inner, m.d), None),
        ("a.wqkv", la, (m.d, (m.hq + 2 * m.hkv) * m.hd), None),
        ("a.wo", la, (m.hq * m.hd, m.d), None),
        ("f.w1", len(m.kinds), (m.d, 2 * m.ffn), None),
        ("f.w2", len(m.kinds), (m.ffn, m.d), None),
        ("embed", 1, (m.vocab, m.d), 0.02),
        ("lm_head", 1, (m.d, m.vocab), None),
    ]


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(keys, mat, scale, dtype):
    def one(k):
        return (jax.random.normal(k, mat, jnp.float32) * scale).astype(dtype)

    return jax.lax.map(one, keys)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw_vectors(keys, width, kind):
    u = jax.vmap(lambda k: jax.random.uniform(k, (width,), jnp.float32))(keys)
    if kind == "a":
        return jnp.log(A_MIN + u * (A_MAX - A_MIN))
    dt = jnp.exp(math.log(DT_MIN) + u * (math.log(DT_MAX) - math.log(DT_MIN)))
    return dt + jnp.log(-jnp.expm1(-dt))


def make_weights(config, seed: int, devices) -> dict:
    m = Dims.of(config)
    layout = _layout(m)
    keys = jax.device_put(
        jax.random.split(jax.random.key(seed), sum(n for _, n, _, _ in layout)),
        NamedSharding(dense.mesh_of(devices), P()))
    w, at = {}, 0
    for name, n, shape, how in layout:
        ks = keys[at: at + n]
        at += n
        if how in ("a", "dt"):
            w[name] = _draw_vectors(ks, shape[0], how)
        else:
            w[name] = _draw(ks, shape,
                            how if how is not None else shape[-2] ** -0.5,
                            m.dtype)
    w["embed"], w["lm_head"] = w["embed"][0], w["lm_head"][0]
    return w


# -- layers ---------------------------------------------------------------

def _mlp(m: Dims, mode: str, x, lw):
    gate, up = jnp.split(dense._mm(dense._rms(x, m.eps), lw["w1"], mode), 2,
                         axis=-1)
    return x + m.res_mult * dense._mm(jax.nn.silu(gate) * up, lw["w2"], mode)


def _attend(m: Dims, q, k, v):
    """Causal attention of ONE sequence, a block of query rows at a
    time: q [S, hq, hd], k and v [S, hkv, hd] -> [S, hq, hd]."""
    S = q.shape[0]
    qb = math.gcd(S, QUERY_BLOCK)
    g = m.hq // m.hkv
    blocks = (q.reshape(S // qb, qb, m.hkv, g, m.hd),
              jnp.arange(S // qb) * qb)

    def block(args):
        qi, s0 = args
        s = jnp.einsum("skgd,tkd->kgst", qi, k,
                       precision="highest") * m.attn_scale
        seen = (s0 + jnp.arange(qb))[:, None] >= jnp.arange(S)[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgst,tkd->skgd", p, v, precision="highest")

    return jax.lax.map(block, blocks).reshape(S, m.hq, m.hd)


def attention(m: Dims, mode: str, x, lw):
    """The attention mixer with its residual: x [B, S, d] float32."""
    B, S, _ = x.shape
    qkv = dense._mm(dense._rms(x, m.eps), lw["wqkv"], mode)
    q, k, v = jnp.split(qkv, [m.hq * m.hd, (m.hq + m.hkv) * m.hd], axis=-1)
    q = q.reshape(B, S, m.hq, m.hd)
    k, v = (a.reshape(B, S, m.hkv, m.hd) for a in (k, v))
    if mode == "int8":  # the cache too: a scale per token and head
        kq, ks = dense._q8(k, -1)
        vq, vs = dense._q8(v, -1)
        k, v = kq.astype(jnp.float32) * ks, vq.astype(jnp.float32) * vs
    o = jax.lax.map(lambda qkv: _attend(m, *qkv), (q, k, v))
    return x + m.res_mult * dense._mm(o.reshape(B, S, m.hq * m.hd), lw["wo"],
                                      mode)


def mamba(m: Dims, mode: str, x, lw):
    """The Mamba-2 mixer with its residual: x [B, S, d] float32, the
    recurrence a position at a time from a zero state."""
    B, S, _ = x.shape
    zxbcdt = dense._mm(dense._rms(x, m.eps), lw["w_in"],
                       "f32" if mode == "bf16_state" else mode)
    z, xbc, dt = jnp.split(zxbcdt, [m.inner, m.inner + m.conv_dim], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (m.taps - 1, 0), (0, 0)))
    conv_w = lw["conv_w"].astype(jnp.float32)
    xbc = jax.nn.silu(sum(padded[:, k: k + S] * conv_w[k]
                          for k in range(m.taps)))     # the bias is nought
    u, b, c = jnp.split(xbc, [m.inner, m.inner + m.state], axis=-1)
    u = u.reshape(B, S, m.heads, m.head_dim)
    delta = jax.nn.softplus(dt + lw["dt_bias"])         # [B, S, H]
    decay = jnp.exp(-delta * jnp.exp(lw["a_log"]))

    def position(s, inp):
        u_t, b_t, c_t, delta_t, decay_t = inp
        s = (decay_t[:, :, None, None] * s
             + (delta_t[:, :, None] * u_t)[..., None] * b_t[:, None, None, :])
        if mode == "bf16_state":
            s = s.astype(jnp.bfloat16).astype(jnp.float32)
        return s, jnp.einsum("bhpn,bn->bhp", s, c_t, precision="highest")

    _, y = jax.lax.scan(
        position, jnp.zeros((B, m.heads, m.head_dim, m.state), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (u, b, c, delta, decay)))
    y = jnp.moveaxis(y, 0, 1) + u                       # D is one
    g = y.reshape(B, S, m.inner) * jax.nn.silu(z)
    g = dense._rms(g, m.eps)                            # its scale is one
    return x + m.res_mult * dense._mm(
        g, lw["w_out"], "f32" if mode == "bf16_state" else mode)


_MIXER = {"mamba": (mamba, ("w_in", "conv_w", "a_log", "dt_bias", "w_out")),
          "attention": (attention, ("wqkv", "wo"))}


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer_at(m, mode, kind, x, mixer_w, mlp_w, li, l):
    """Layer ``l`` of all, the ``li``-th of its kind."""
    fn, names = _MIXER[kind]
    lw = {k: jax.lax.dynamic_index_in_dim(mixer_w[k], li, 0, keepdims=False)
          for k in names}
    x = fn(m, mode, x, lw)
    return _mlp(m, "f32" if mode == "bf16_state" else mode, x, {
        k: jax.lax.dynamic_index_in_dim(mlp_w[k], l, 0, keepdims=False)
        for k in ("w1", "w2")})


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(m, embed, tokens):
    return dense._embed(embed, tokens) * m.embed_mult


def forward_logits(m: Dims, weights: dict, tokens, rows, cols, *,
                   mode: str = "f32") -> jax.Array:
    """Full forward pass over ``tokens [B, S]`` (right-padded; the
    recurrence and the causal mask keep the padding out of every real
    position) and the logits at ``(rows[n], cols[n])``: [N, V] float32.
    Positions past every row's last asked-for column are cut off."""
    tokens, rows, cols = (np.asarray(a) for a in (tokens, rows, cols))
    S = min(tokens.shape[1],
            -(-(int(cols.max()) + 1) // LENGTH_GRAIN) * LENGTH_GRAIN)
    x = _embed(m, weights["embed"], jnp.asarray(tokens[:, :S], jnp.int32))
    by_kind = {"mamba": {k[2:]: v for k, v in weights.items()
                         if k.startswith("m.")},
               "attention": {k[2:]: v for k, v in weights.items()
                             if k.startswith("a.")}}
    mlp_w = {"w1": weights["f.w1"], "w2": weights["f.w2"]}
    seen = {"mamba": 0, "attention": 0}
    for l, kind in enumerate(m.kinds):
        x = _layer_at(m, mode, kind, x, by_kind[kind], mlp_w,
                      jnp.int32(seen[kind]), jnp.int32(l))
        seen[kind] += 1
    return dense._head(m, "f32" if mode == "bf16_state" else mode, x,
                       jnp.asarray(rows, jnp.int32),
                       jnp.asarray(cols, jnp.int32),
                       weights["lm_head"]) / m.logit_div


def judge(config, weights: dict, samples: list, pad_to: int, rows_pad: int,
          **kw) -> dict:
    m = Dims.of(config)
    return dense.judge_with(
        lambda tokens, rows, cols, mode: forward_logits(
            m, weights, tokens, rows, cols, mode=mode),
        samples, pad_to, rows_pad, **kw)
