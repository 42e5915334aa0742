"""Decoder block library: the parts of today's open decoder-only language
models, as layers.

``RMSNorm``; ``rotary_embedding`` (a function: it has no weights);
``GroupedQueryAttention`` (key-value heads fewer than query heads, optional
per-head RMS norm of q and k, optional sigmoid output gate, causal, a sliding
window or full, rotary positions or none); ``SwiGLU``; and ``DecoderBlock``,
which wires them with four norms a layer around either a dense ``SwiGLU`` or
a ``SparseMoE`` (keras/layers/moe.py):

    h += post_attn_norm(attention(in_norm(h)))
    h += post_mlp_norm(mlp(pre_mlp_norm(h)))

Mixed precision is the block's own: master weights stay float32 in the
optimizer; a block casts the weights it is about to use to ``dtype`` inside
its (rematerialised) body, so the cast copy lives as long as the block's pass
and no whole-model copy exists. Norm statistics, softmax statistics and router
scores stay float32. Attention goes through
``ops.scaled_dot_product_attention`` (``window=`` for sliding layers): the
Pallas flash kernels past the size threshold on the chip, XLA elsewhere.
"""

from __future__ import annotations

import itertools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.keras.engine.base import KerasLayer, Shape, unique_name
from analytics_zoo_tpu.keras.layers.moe import DECODER_INIT, SparseMoE
from analytics_zoo_tpu.ops.attention import scaled_dot_product_attention


def rms_norm(x, gain, eps: float):
    """Last-dim RMS norm: float32 statistics, the result in x's dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rotary_embedding(x, theta: float = 10000.0, positions=None):
    """Rotary position embedding (Su et al. 2021) in the half-rotated form:
    x (..., seq, head_dim); position t of a row is t unless ``positions``
    (seq,) says otherwise. Angles in float32, the result in x's dtype."""
    s, hd = x.shape[-2], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    pos = (jnp.arange(s, dtype=jnp.float32) if positions is None
           else positions.astype(jnp.float32))
    ang = pos[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : hd // 2], xf[..., hd // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * jnp.cos(ang) + rotated * jnp.sin(ang)).astype(x.dtype)


def _cast(params, dtype):
    if dtype is None:
        return params
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, params)


class RMSNorm(KerasLayer):
    """Root-mean-square norm over the last dim with a learned gain (Zhang &
    Sennrich 2019)."""

    def __init__(self, epsilon: float = 1e-5, input_shape=None, name=None):
        super().__init__(input_shape, name or unique_name("rmsnorm"))
        self.epsilon = epsilon

    def build(self, input_shape: Shape):
        self.add_weight("gain", (input_shape[-1],), "ones")

    def call(self, params, x, **kw):
        return rms_norm(x, params["gain"], self.epsilon)


class SwiGLU(KerasLayer):
    """Gated feed-forward ``W_down(silu(W_gate x) * W_up x)``, no biases;
    gate and up side by side in one kernel ``w_gate_up`` (d, 2 * width)."""

    def __init__(self, width: int, input_shape=None, name=None):
        super().__init__(input_shape, name or unique_name("swiglu"))
        self.width = int(width)

    def build(self, input_shape: Shape):
        d, init = input_shape[-1], DECODER_INIT
        self.add_weight("w_gate_up", (d, 2 * self.width), init)
        self.add_weight("w_down", (self.width, d), init)

    def call(self, params, x, **kw):
        gate, up = jnp.split(x @ params["w_gate_up"], 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ params["w_down"]


class GroupedQueryAttention(KerasLayer):
    """Causal self-attention over (B, S, d) with ``n_kv_head`` key-value
    heads shared by ``n_head`` query heads. ``qk_norm``: RMS norm of q and k
    over a head's width, learned gain. ``gated``: a fourth projection g, the
    output is ``W_o (attn * sigmoid(g))``. ``window``: None for full causal
    attention, else query i sees keys i - window < j <= i. ``rope_theta``:
    None for no positional encoding, else rotary embedding on q and k. One
    fused input kernel ``w_in`` (d, (2 n_head [gated] or n_head + 2 n_kv_head)
    * head_dim) laid out q | k | v | g."""

    def __init__(self, n_head: int, n_kv_head: int, head_dim: int,
                 window: Optional[int] = None,
                 rope_theta: Optional[float] = None, qk_norm: bool = True,
                 gated: bool = True, epsilon: float = 1e-5,
                 input_shape=None, name=None):
        super().__init__(input_shape, name or unique_name("gqa"))
        if n_head % n_kv_head:
            raise ValueError(f"{n_head} query heads do not share "
                             f"{n_kv_head} key-value heads evenly")
        self.n_head, self.n_kv_head, self.head_dim = n_head, n_kv_head, head_dim
        self.window, self.rope_theta = window, rope_theta
        self.qk_norm, self.gated = qk_norm, gated
        self.epsilon = epsilon

    def _splits(self) -> Tuple[int, ...]:
        q, kv = self.n_head * self.head_dim, self.n_kv_head * self.head_dim
        return (q, kv, kv) + ((q,) if self.gated else ())

    def build(self, input_shape: Shape):
        d, init = input_shape[-1], DECODER_INIT
        self.add_weight("w_in", (d, sum(self._splits())), init)
        self.add_weight("w_out", (self.n_head * self.head_dim, d), init)
        if self.qk_norm:
            self.add_weight("q_norm", (self.head_dim,), "ones")
            self.add_weight("k_norm", (self.head_dim,), "ones")

    def call(self, params, x, **kw):
        b, s, _ = x.shape
        hd = self.head_dim
        parts = jnp.split(x @ params["w_in"],
                          list(itertools.accumulate(self._splits()))[:-1],
                          axis=-1)

        def heads(t, n):
            return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

        q, k = heads(parts[0], self.n_head), heads(parts[1], self.n_kv_head)
        v = heads(parts[2], self.n_kv_head)
        if self.qk_norm:
            q = rms_norm(q, params["q_norm"], self.epsilon)
            k = rms_norm(k, params["k_norm"], self.epsilon)
        if self.rope_theta is not None:
            q = rotary_embedding(q, self.rope_theta)
            k = rotary_embedding(k, self.rope_theta)
        with jax.named_scope("attn.full" if self.window is None
                             else "attn.window"):
            o = scaled_dot_product_attention(q, k, v, causal=True,
                                             window=self.window)
        o = o.transpose(0, 2, 1, 3).reshape(b, s, self.n_head * hd)
        if self.gated:
            o = o * jax.nn.sigmoid(parts[3].astype(jnp.float32)).astype(o.dtype)
        return o @ params["w_out"]


class DecoderBlock(KerasLayer):
    """One pre- and post-normed decoder layer (see the module docstring).
    ``mlp``: a built-or-unbuilt ``SwiGLU`` or ``SparseMoE``. ``dtype``: the
    compute type its weights and input are cast to inside the block.
    ``remat``: rematerialise each half in the backward pass (a layer's
    activations then live only while its gradient is computed). Of the
    attention half a block keeps what only the flash kernel can make, its
    output and log-sum-exp (``ops.flash_attention.FLASH_RESIDUALS``; 136 MB
    a layer at 16 384 tokens of 32 heads x 128), and recomputes the norm,
    the projections and rotary, not the kernel; on the XLA path (short
    rows) it keeps nothing and recomputes the whole half. Parameters
    nest: ``{"attn": ..., "mlp": ..., "<norm>": {"gain": ...}}``. A block with
    an expert layer carries that layer's state (the router's selection bias
    and the step's tokens an expert) and returns it updated when training."""

    NORMS = ("in_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")

    def __init__(self, attn: GroupedQueryAttention, mlp: KerasLayer,
                 epsilon: float = 1e-5, dtype: Optional[str] = "bfloat16",
                 remat: bool = True, input_shape=None, name=None):
        super().__init__(input_shape, name or unique_name("decoder_block"))
        self.attn, self.mlp = attn, mlp
        self.epsilon, self.remat = epsilon, remat
        self.dtype = None if dtype is None else jnp.dtype(dtype)
        self.has_state = bool(getattr(mlp, "has_state", False))

    def build(self, input_shape: Shape):
        self.attn.ensure_built(input_shape)
        self.mlp.ensure_built(input_shape)
        self._norms = {n: RMSNorm(self.epsilon, name=f"{self.name}_{n}")
                       for n in self.NORMS}
        for norm in self._norms.values():
            norm.ensure_built(input_shape)

    def _parts(self):
        return {"attn": self.attn, "mlp": self.mlp, **self._norms}

    def init_params(self, rng):
        return {key: part.init_params(jax.random.fold_in(rng, i))
                for i, (key, part) in enumerate(self._parts().items())}

    def param_pspecs(self):
        return {key: part.param_pspecs() for key, part in self._parts().items()}

    def init_state(self):
        return self.mlp.init_state() if self.has_state else {}

    def call(self, params, x, state=None, training=False, **kw):
        eps = self.epsilon

        def attn_half(p, h):
            p = _cast(p, self.dtype)
            a = self.attn.call(p["attn"],
                               rms_norm(h, p["in_norm"]["gain"], eps))
            return h + rms_norm(a, p["post_attn_norm"]["gain"], eps)

        def mlp_half(p, h, st):
            p = _cast(p, self.dtype)
            m = rms_norm(h, p["pre_mlp_norm"]["gain"], eps)
            if self.has_state:
                y, st = self.mlp.call(p["mlp"], m, state=st, training=training)
            else:
                y = self.mlp.call(p["mlp"], m)
            return h + rms_norm(y, p["post_mlp_norm"]["gain"], eps), st

        # What the attention half keeps is in the class's docstring. The
        # other half keeps nothing: its forward pass, the expert layer's
        # compacted pass included, runs twice (overflow chunks, rematerialised
        # one by one inside, three times).
        if self.remat:
            # imported here like every use of the kernels' module: it brings
            # Pallas, a second of import that a model on the XLA path skips
            from analytics_zoo_tpu.ops.flash_attention import FLASH_RESIDUALS

            attn_half = jax.checkpoint(
                attn_half,
                policy=jax.checkpoint_policies.save_only_these_names(
                    *FLASH_RESIDUALS))
            mlp_half = jax.checkpoint(mlp_half)
        if self.dtype is not None:
            x = x.astype(self.dtype)
        attn_keys = ("attn", "in_norm", "post_attn_norm")
        h = attn_half({k: params[k] for k in attn_keys}, x)
        h, new_state = mlp_half(
            {k: v for k, v in params.items() if k not in attn_keys}, h,
            state if self.has_state else None)
        return (h, new_state) if self.has_state else h
