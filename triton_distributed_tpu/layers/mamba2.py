"""The Mamba-2 mixer over a per-slot recurrent state.

Per token, ``u`` the layer's normed input (``di = H x P`` inner
channels in ``H`` heads of ``P``, a state of ``N`` a channel, ONE group:
``B`` and ``C`` are shared by every head):

    [z | xBC | dt] = u W_in                  di | di + 2 N | H, no bias
    xBC = silu(conv(xBC))                    causal, depthwise, ``taps``
                                             wide, with bias, zeros
                                             before the start
    [x | B | C] = xBC                        x [H, P], B [N], C [N]
    delta = softplus(dt + dt_bias) [H]       A = -exp(A_log) [H]
    S_t = exp(delta_t A) S_{t-1} + delta_t x_t (x) B_t        S [H, P, N]
    y_t = S_t C_t + D x_t
    out = (rms(y * silu(z)) * w) W_out       the norm over all of di

A slot keeps ``S`` (float32) and the convolution's last ``taps - 1``
inputs between programs (``PagedKVCache.ssm_state [Lm, B, H, P, N]`` /
``conv_state [Lm, taps - 1, B, C]``, indexed by (recurrent layer, slot) and addressed in place off the layer
scan's carry). A decode step moves them by one position for the rows IN
FLIGHT (:func:`mamba2_decode`, the state through ``tdt_ssm_decode``); a
prefill chunk computes the same recurrence in its chunked
(state-space-duality) form as batched matrix products
(:func:`mamba2_chunk`): inside a block of ``chunk`` positions ``Y = ((C
B^T) * L)(delta * x)`` with ``L[t, s] = exp(sum_{s < r <= t} delta_r
A)``, between blocks the carried ``S``. ``delta``, ``exp(delta A)``,
every cumulative sum and ``S`` are float32; the projections and the
convolution's output are the served dtype.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from triton_distributed_tpu.models.config import ModelConfig
from triton_distributed_tpu.ops.ssm.decode import ssm_decode
from triton_distributed_tpu.runtime.pytree import register_param_dataclass


@dataclasses.dataclass
class Mamba2Params:
    w_in: jax.Array     # [d, 2 di + 2 N + H]: z | x | B | C | dt
    conv_w: jax.Array   # [taps, di + 2 N]: tap k meets the input k - taps + 1 back
    conv_b: jax.Array   # [di + 2 N]
    dt_bias: jax.Array  # [H] f32
    a_log: jax.Array    # [H] f32
    d_skip: jax.Array   # [H] f32
    norm: jax.Array     # [di]
    w_out: jax.Array    # [di, d]


MAMBA2_FIELDS = ["w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip",
                 "norm", "w_out"]
register_param_dataclass(Mamba2Params, MAMBA2_FIELDS)


@dataclasses.dataclass(frozen=True)
class Mamba2Dims:
    heads: int
    head_dim: int
    state: int
    taps: int
    chunk: int
    eps: float

    @classmethod
    def of(cls, cfg: ModelConfig) -> "Mamba2Dims":
        if cfg.mamba_n_groups != 1:
            raise ValueError(
                f"mamba_n_groups {cfg.mamba_n_groups}: the mixer shares "
                "one B and C among all heads (one group)")
        return cls(heads=cfg.mamba_n_heads, head_dim=cfg.mamba_d_head,
                   state=cfg.mamba_d_state, taps=cfg.mamba_d_conv,
                   chunk=cfg.mamba_chunk_size, eps=cfg.rms_eps)

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.state

    @property
    def in_dim(self) -> int:
        return self.inner + self.conv_dim + self.heads


def _project(p: Mamba2Params, u, m: Mamba2Dims):
    """``u [T, d]`` to ``z [T, di]``, ``xBC [T, di + 2 N]`` (before the
    convolution), ``dt [T, H]``, in the served dtype."""
    zxbcdt = jnp.dot(u, p.w_in, preferred_element_type=jnp.float32).astype(
        u.dtype)
    return jnp.split(zxbcdt, [m.inner, m.inner + m.conv_dim], axis=-1)


def _conv(p: Mamba2Params, window, m: Mamba2Dims, dtype):
    """The convolution's output at one position from its ``taps``
    inputs ``window [taps, ..., C]`` (oldest first), with bias and
    silu, rounded to the served dtype."""
    acc = jnp.einsum("k...c,kc->...c", window.astype(jnp.float32),
                     p.conv_w.astype(jnp.float32))
    return jax.nn.silu(acc + p.conv_b.astype(jnp.float32)).astype(dtype)


def _split_xbc(xbc, m: Mamba2Dims):
    x, b, c = jnp.split(xbc.astype(jnp.float32),
                        [m.inner, m.inner + m.state], axis=-1)
    return x.reshape(*x.shape[:-1], m.heads, m.head_dim), b, c


def _delta(p: Mamba2Params, dt):
    return jax.nn.softplus(dt.astype(jnp.float32) + p.dt_bias)


def _gated_out(p: Mamba2Params, y, z, m: Mamba2Dims, dtype):
    """``y [T, H, P]`` f32 (the skip included) through the gated norm
    and the output projection: ``[T, d]``."""
    g = y.reshape(y.shape[0], m.inner) * jax.nn.silu(z.astype(jnp.float32))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + m.eps)
    g = (g * p.norm.astype(jnp.float32)).astype(dtype)
    return jnp.dot(g, p.w_out, preferred_element_type=jnp.float32).astype(
        dtype)


def mamba2_decode(p: Mamba2Params, u, state, layer, m: Mamba2Dims, *,
                  live, rows, n):
    """One position for every slot: ``u [B, d]``, ``state =
    (ssm_state, conv_state)`` whole, ``layer`` the recurrent layer
    addressed. Only the rows ``live [B]`` marks move their state and
    their convolution's inputs (``rows`` / ``n``:
    :func:`live_rows` of it, made once a step). Returns ``(out [B, d],
    state)``."""
    ssm, conv = state
    z, xbc, dt = _project(p, u, m)
    tail = jax.lax.dynamic_index_in_dim(conv, layer, 0, keepdims=False)
    window = jnp.concatenate([tail, xbc[None].astype(tail.dtype)], axis=0)
    conv = jax.lax.dynamic_update_slice(
        conv, jnp.where(live[None, :, None], window[1:], tail)[None],
        (layer, 0, 0, 0))                               # [taps - 1, B, C]
    x, b, c = _split_xbc(_conv(p, window, m, u.dtype), m)
    delta = _delta(p, dt)                               # [B, H]
    y, ssm = ssm_decode(
        ssm, jnp.exp(-delta * jnp.exp(p.a_log)), delta[:, :, None] * x, b, c,
        rows, n, layer=layer)
    y = y + p.d_skip[None, :, None] * x
    return _gated_out(p, y, z, m, u.dtype), (ssm, conv)


def ssd_chunked(x, delta, a, b, c, s0, block: int):
    """The recurrence over ``T`` positions of one sequence in its
    chunked form, all float32: ``x [T, H, P]``, ``delta [T, H]`` (nought
    at a position that must not move the state), ``a [H]`` (negative),
    ``b``, ``c`` ``[T, N]``, ``s0 [H, P, N]`` the state before. ``T`` is
    a multiple of ``block``. Returns ``(y [T, H, P]`` without the skip,
    the state after)``."""
    t, h, p = x.shape
    nb, q = t // block, block
    cum = jnp.cumsum((delta * a).reshape(nb, q, h), axis=1)   # [nb, q, H]
    cum_h = cum.swapaxes(1, 2)                                # [nb, H, q]
    xdt = (x * delta[:, :, None]).reshape(nb, q, h, p)
    bc, cc = b.reshape(nb, q, -1), c.reshape(nb, q, -1)
    # Inside a block: ((C B^T) * L) (delta x).
    seen = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(
        seen, cum_h[:, :, :, None] - cum_h[:, :, None, :], -jnp.inf))
    mix = jnp.einsum("ctn,csn->cts", cc, bc)[:, None] * decay  # [nb, H, q, q]
    y = jnp.einsum("chts,cshp->cthp", mix, xdt)
    # What each block adds to the state by its end, and the carry.
    to_end = jnp.exp(cum[:, -1:, :] - cum)                    # [nb, q, H]
    added = jnp.einsum("csn,cshp->chpn", bc, xdt * to_end[..., None])
    block_decay = jnp.exp(cum[:, -1, :])                      # [nb, H]

    def carry(s, inp):
        add, dec = inp
        return dec[:, None, None] * s + add, s

    s_end, s_before = jax.lax.scan(carry, s0, (added, block_decay))
    y = y + (jnp.einsum("ctn,chpn->cthp", cc, s_before)
             * jnp.exp(cum)[..., None])
    return y.reshape(t, h, p), s_end


def mamba2_chunk(p: Mamba2Params, u, state, layer, m: Mamba2Dims, *,
                 slot, q_offset, n_real):
    """A prefill chunk of ONE slot: ``u [C, d]`` are positions
    ``q_offset + i``, of which the first ``n_real`` are the prompt's
    (the rest right-padding: ``delta = 0`` there leaves ``S`` as it
    was, and the convolution's tail is taken at the last real inputs).
    The slot's state is read unless ``q_offset == 0`` (a fresh
    admission starts from zeros, whoever held the slot before) and
    written back absolutely. Returns ``(out [C, d], state)``."""
    ssm, conv = state
    n_tok = u.shape[0]
    block = min(m.chunk, n_tok)
    if n_tok % block:
        raise ValueError(
            f"a chunk of {n_tok} positions is no multiple of the "
            f"recurrence's block of {block}")
    z, xbc, dt = _project(p, u, m)
    fresh = q_offset == 0
    tail = jax.lax.dynamic_slice(
        conv, (layer, 0, slot, 0),
        (1, m.taps - 1, 1, conv.shape[3]))[0, :, 0]     # [taps - 1, C]
    tail = jnp.where(fresh, jnp.zeros_like(tail), tail)
    padded = jnp.concatenate([tail, xbc.astype(tail.dtype)], axis=0)
    conv = jax.lax.dynamic_update_slice(
        conv,
        jax.lax.dynamic_slice_in_dim(
            padded, n_real, m.taps - 1)[None, :, None],
        (layer, 0, slot, 0))
    window = jnp.stack(
        [padded[k: k + n_tok] for k in range(m.taps)])  # [taps, C, .]
    x, b, c = _split_xbc(_conv(p, window, m, u.dtype), m)
    real = jnp.arange(n_tok, dtype=jnp.int32) < n_real
    delta = jnp.where(real[:, None], _delta(p, dt), 0.0)
    s0 = jax.lax.dynamic_slice(
        ssm, (layer, slot, 0, 0, 0), (1, 1, *ssm.shape[2:]))[0, 0]
    y, s_end = ssd_chunked(
        x, delta, -jnp.exp(p.a_log), b, c,
        jnp.where(fresh, jnp.zeros_like(s0), s0), block)
    ssm = jax.lax.dynamic_update_slice(
        ssm, s_end[None, None], (layer, slot, 0, 0, 0))
    y = y + p.d_skip[None, :, None] * x
    return _gated_out(p, y, z, m, u.dtype), (ssm, conv)
