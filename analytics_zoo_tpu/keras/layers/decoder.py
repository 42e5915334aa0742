"""Decoder block library: the parts of today's open decoder-only language
models, as layers.

``RMSNorm``; ``rotary_embedding`` (a function: it has no weights); four token
mixers, ``GroupedQueryAttention`` (key-value heads fewer than query heads,
optional per-head RMS norm of q and k, optional sigmoid output gate, causal, a
sliding window or full, rotary positions or none), ``LatentAttention`` (keys
and values of every head from one low-rank latent a token, one rotary key
shared by all heads, queries and keys wider than values), ``GatedShortConv``
(a gated causal convolution over a few neighbouring tokens, no attention at
all) and ``Mamba2Mixer`` (a Mamba-2 state-space layer, its scan in the chunked
form of ``ops/ssd.py``); ``SwiGLU``; and ``DecoderBlock``, which wires one
mixer and either a dense ``SwiGLU`` or a ``SparseMoE`` (keras/layers/moe.py)
under one of two norm layouts, four norms a layer (``"sandwich"``) or the two
pre-norms alone (``"pre"``), or one of the two parts alone behind its
pre-norm (the Nemotron-H layers):

    h += post_attn_norm(mixer(in_norm(h)))      |  h += mixer(in_norm(h))
    h += post_mlp_norm(mlp(pre_mlp_norm(h)))    |  h += mlp(pre_mlp_norm(h))

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
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.keras.engine.base import KerasLayer, Shape, unique_name
from analytics_zoo_tpu.keras.layers.moe import DECODER_INIT, SparseMoE
from analytics_zoo_tpu.ops.attention import scaled_dot_product_attention
from analytics_zoo_tpu.ops.rotary import Rotary, as_rotary, frequencies
from analytics_zoo_tpu.ops.ssd import chunked_scan


def rms_norm(x, gain, eps: float):
    """Last-dim RMS norm: float32 statistics, the result in x's dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def rotary_embedding(x, theta=10000.0, positions=None,
                     interleaved: bool = False):
    """Rotary position embedding (Su et al. 2021): x (..., seq, head_dim);
    position t of a row is t unless ``positions`` (seq,) says otherwise.
    ``theta``: a base, or an ``ops.rotary.Rotary`` spec (YaRN's scaled
    frequencies and magnitude). Frequency i turns one pair of x's entries by
    ``t * inv_i`` (``ops.rotary.frequencies``; ``inv_i = 1 / theta^(2i /
    head_dim)`` unscaled): in the half-rotated form the pair ``(x[i], x[i +
    head_dim / 2])``, ``interleaved`` the neighbours ``(x[2i], x[2i + 1])``.
    Angles in float32, the result in x's dtype."""
    s, hd = x.shape[-2], x.shape[-1]
    inv, magnitude = frequencies(as_rotary(theta), hd)
    pos = (jnp.arange(s, dtype=jnp.float32) if positions is None
           else positions.astype(jnp.float32))
    ang = pos[:, None] * inv[None]
    xf = x.astype(jnp.float32)
    if interleaved:
        ang = jnp.repeat(ang, 2, axis=-1)
        pairs = xf.reshape(*xf.shape[:-1], hd // 2, 2)
        rotated = jnp.stack([-pairs[..., 1], pairs[..., 0]],
                            axis=-1).reshape(xf.shape)
    else:
        ang = jnp.concatenate([ang, ang], axis=-1)
        x1, x2 = xf[..., : hd // 2], xf[..., hd // 2:]
        rotated = jnp.concatenate([-x2, x1], axis=-1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    return (xf * cos + rotated * sin).astype(x.dtype)


def _flash_residuals() -> Tuple[str, ...]:
    """The names an attention mixer's half keeps under a block's checkpoint.
    Imported here like every use of the kernels' module: it brings Pallas, a
    second of import that a model on the XLA path skips."""
    from analytics_zoo_tpu.ops.flash_attention import FLASH_RESIDUALS

    return FLASH_RESIDUALS


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
    None for no positional encoding, else rotary embedding on q and k by a
    base or by an ``ops.rotary.Rotary`` spec (YaRN's scaled frequencies and
    magnitude among them). One
    fused input kernel ``w_in`` (d, (2 n_head [gated] or n_head + 2 n_kv_head)
    * head_dim) laid out q | k | v | g. With ``qk_norm`` the head norm and
    rotary of q and of k are one op, ``ops.qk_rotary.norm_rotary`` (fused
    kernels on the chip), which also moves them into heads. Scopes, none
    inside another: the input projection and the split under
    ``attn.proj_in``, the head norms and rotary under ``attn.qk_rotary``,
    the kernel call under
    ``attn.window`` or ``attn.full``, the heads merged, the gate and ``w_out``
    under ``attn.proj_out``."""

    block_key = "attn"      # a mixer's place in a block's parameters

    def __init__(self, n_head: int, n_kv_head: int, head_dim: int,
                 window: Optional[int] = None,
                 rope_theta: Union[None, float, Rotary] = None,
                 qk_norm: bool = True,
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

    def kept_residuals(self) -> Tuple[str, ...]:
        """What a block's rematerialised mixer half keeps of this mixer: what
        only the flash kernel can make, its output and log-sum-exp (on the
        XLA path, short rows, the names are nowhere and nothing is kept)."""
        return _flash_residuals()

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

        def split(t, n):
            return t.reshape(b, s, n, hd)

        def heads(t, n):
            return split(t, n).transpose(0, 2, 1, 3)

        with jax.named_scope("attn.proj_in"):
            parts = jnp.split(x @ params["w_in"],
                              list(itertools.accumulate(self._splits()))[:-1],
                              axis=-1)
            # the head norms take q and k as the projection lays them out
            qk = split if self.qk_norm else heads
            q, k = qk(parts[0], self.n_head), qk(parts[1], self.n_kv_head)
            v = heads(parts[2], self.n_kv_head)
        with jax.named_scope("attn.qk_rotary"):
            if self.qk_norm:
                # imported here like the kernels' module (_flash_residuals)
                from analytics_zoo_tpu.ops.qk_rotary import norm_rotary

                q = norm_rotary(q, params["q_norm"], self.epsilon,
                                self.rope_theta)
                k = norm_rotary(k, params["k_norm"], self.epsilon,
                                self.rope_theta)
            elif self.rope_theta is not None:
                q = rotary_embedding(q, self.rope_theta)
                k = rotary_embedding(k, self.rope_theta)
        with jax.named_scope("attn.full" if self.window is None
                             else "attn.window"):
            o = scaled_dot_product_attention(q, k, v, causal=True,
                                             window=self.window)
        with jax.named_scope("attn.proj_out"):
            o = o.transpose(0, 2, 1, 3).reshape(b, s, self.n_head * hd)
            if self.gated:
                o = o * jax.nn.sigmoid(parts[3].astype(jnp.float32)).astype(
                    o.dtype)
            return o @ params["w_out"]


class LatentAttention(KerasLayer):
    """Causal multi-head latent attention over (B, S, d) with no query
    compression (DeepSeek-V2's MLA, ``q_lora_rank`` null): a token's keys and
    values of all ``n_head`` heads come from one latent ``kv_rank`` wide, and
    the key's rotary part is ONE head ``qk_rope_dim`` wide that every query
    head shares.

        q  = u W_q                    (d -> H x (nope + rope))    [q_nope | q_rope] a head
        a  = u W_kv_a                 (d -> kv_rank + rope)       [c | k_rope]
        kv = rms(c; kv_norm) W_kv_b   (kv_rank -> H x (nope + v)) [k_nope | v] a head
        q_h = [q_nope_h | rot(q_rope_h)],  k_h = [k_nope_h | rot(k_rope)]
        o_h = softmax(q_h k_h^T / sqrt(nope + rope) + causal) v_h
        y  = [o_1 .. o_H] W_out       (H x v -> d)

    ``rot`` is the rotary embedding over neighbouring pairs. Queries and keys are ``qk_nope_dim +
    qk_rope_dim`` wide, values ``v_dim``: the flash kernels take the two
    widths apart. The shared key is broadcast to the heads before the kernel,
    so its gradient is the sum over heads. No biases. Everything between the
    block's norm and the kernel's operands runs under the scope
    ``attn.latent``, the kernel call under ``attn.full`` (it is full causal
    attention), the heads merged and ``W_out`` under ``attn.proj_out``."""

    block_key = "attn"

    def __init__(self, n_head: int, qk_nope_dim: int, qk_rope_dim: int,
                 v_dim: int, kv_rank: int, rope_theta: float = 10000.0,
                 epsilon: float = 1e-6, input_shape=None, name=None):
        super().__init__(input_shape, name or unique_name("latent_attn"))
        if qk_rope_dim % 2:
            raise ValueError(f"rotary pairs over {qk_rope_dim} entries")
        self.n_head, self.v_dim, self.kv_rank = n_head, v_dim, kv_rank
        self.qk_nope_dim, self.qk_rope_dim = qk_nope_dim, qk_rope_dim
        self.rope_theta, self.epsilon = rope_theta, epsilon

    @property
    def qk_dim(self) -> int:
        """A head's width in queries and keys: the part with no position
        beside the rotary part."""
        return self.qk_nope_dim + self.qk_rope_dim

    def kept_residuals(self) -> Tuple[str, ...]:
        """As ``GroupedQueryAttention``: the flash kernel's output and
        log-sum-exp; the projections, the latent norm, rotary and the
        broadcast are recomputed."""
        return _flash_residuals()

    def build(self, input_shape: Shape):
        d, init, h = input_shape[-1], DECODER_INIT, self.n_head
        self.add_weight("w_q", (d, h * self.qk_dim), init)
        self.add_weight("w_kv_a", (d, self.kv_rank + self.qk_rope_dim), init)
        self.add_weight("kv_norm", (self.kv_rank,), "ones")
        self.add_weight("w_kv_b",
                        (self.kv_rank, h * (self.qk_nope_dim + self.v_dim)),
                        init)
        self.add_weight("w_out", (h * self.v_dim, d), init)

    def call(self, params, x, **kw):
        b, s, _ = x.shape
        h, nope = self.n_head, self.qk_nope_dim

        def heads(t):
            return t.reshape(b, s, h, -1).transpose(0, 2, 1, 3)

        def rot(t):
            return rotary_embedding(t, self.rope_theta, interleaved=True)

        with jax.named_scope("attn.latent"):
            q = heads(x @ params["w_q"])
            c, k_rope = jnp.split(x @ params["w_kv_a"], [self.kv_rank],
                                  axis=-1)
            kv = heads(rms_norm(c, params["kv_norm"], self.epsilon)
                       @ params["w_kv_b"])
            k_nope, v = kv[..., :nope], kv[..., nope:]
            q = jnp.concatenate([q[..., :nope], rot(q[..., nope:])], axis=-1)
            k_rope = jnp.broadcast_to(rot(k_rope)[:, None],
                                      (b, h, s, self.qk_rope_dim))
            k = jnp.concatenate([k_nope, k_rope], axis=-1)
        with jax.named_scope("attn.full"):
            o = scaled_dot_product_attention(q, k, v, causal=True,
                                             scale=self.qk_dim ** -0.5)
        with jax.named_scope("attn.proj_out"):
            return o.transpose(0, 2, 1, 3).reshape(
                b, s, h * self.v_dim) @ params["w_out"]


class GatedShortConv(KerasLayer):
    """Gated short convolution over (B, S, d), the token mixer of the
    conv-attention hybrids (LFM2): ``[B | C | X] = W_in u`` (d -> 3d, no
    bias), ``z = B * X``, a causal depthwise convolution over the ``kernel``
    newest tokens, ``c[t] = sum_j taps[:, j] * z[t - (kernel - 1) + j]`` with
    ``z`` nought before the row's first token, and ``y = W_out (C * c)``: no
    activation, no bias, no position. A token sees ``kernel - 1`` tokens
    back and none ahead. Plain ``jax.numpy`` that XLA fuses (one pad,
    ``kernel`` shifted slices), all of it under the scope ``conv.short``."""

    block_key = "conv"

    def __init__(self, kernel: int = 3, input_shape=None, name=None):
        super().__init__(input_shape, name or unique_name("short_conv"))
        if kernel < 1:
            raise ValueError(f"a convolution over {kernel} tokens")
        self.kernel = int(kernel)

    def build(self, input_shape: Shape):
        d, init = input_shape[-1], DECODER_INIT
        self.add_weight("w_in", (d, 3 * d), init)
        self.add_weight("taps", (d, self.kernel), init)
        self.add_weight("w_out", (d, d), init)

    def kept_residuals(self) -> Tuple[str, ...]:
        """Nothing: the whole half is recomputed, like a feed-forward."""
        return ()

    def call(self, params, x, **kw):
        s = x.shape[1]
        with jax.named_scope("conv.short"):
            b, c, xx = jnp.split(x @ params["w_in"], 3, axis=-1)
            z = jnp.pad(b * xx, ((0, 0), (self.kernel - 1, 0), (0, 0)))
            taps = params["taps"]
            conv = sum(taps[:, j] * z[:, j:j + s] for j in range(self.kernel))
            return (c * conv) @ params["w_out"]


def _uniform_init(bound: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)

    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    """log(1 .. H): head j decays at rate j + 1."""
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


def _dt_bias_init(lo: float, hi: float, floor: float):
    """The inverse softplus of dt drawn log-uniform in [lo, hi], floored."""
    def init(key, shape, dtype=jnp.float32):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (
            jnp.log(hi) - jnp.log(lo)) + jnp.log(lo))
        dt = jnp.maximum(dt, floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


class Mamba2Mixer(KerasLayer):
    """The Mamba-2 token mixer over (B, S, d) (Dao & Gu 2024), as the
    Nemotron-H layers hold it, no biases but the convolution's:

        [z | xBC | dl] = W_in u        (d -> H P + (H P + 2 G N) + H)
        xBC <- silu(causal depthwise conv over ``conv_kernel`` tokens + b)
        [x | B | C] = xBC              (H heads of P; G groups of N)
        dt = softplus(dl + dt_bias);   A = -exp(A_log)
        y = chunked_scan(x, dt, A, B, C, D)        (ops/ssd.py)
        out = W_out rms_G(y * silu(z); norm)       (the gate, then a norm over
                                                    G groups of H P / G)

    Head j reads group ``j // (H / G)`` of B and C and keeps a P x N state.
    ``dt``, the decays and the states are float32, and so are ``a_log``,
    ``dt_bias`` and ``d_skip`` whatever the block's compute type
    (``float32_params``). Initial values are the family's: ``a_log`` =
    log(1 .. H), ``d_skip`` = 1, ``dt_bias`` the inverse softplus of dt
    log-uniform in ``time_step`` = (min, max) floored at its third entry,
    the convolution's taps and bias uniform in +-1 / sqrt(kernel), the
    matrices normal(0.02), ``w_out`` times ``out_scale``. A row must be a
    whole number of ``chunk`` tokens. Scopes, none inside another:
    ``ssm.proj_in`` (the input projection, the convolution, its silu and
    dt), ``ssm.scan`` (the scan with the D skip), ``ssm.proj_out`` (the
    gate, the grouped norm and ``w_out``)."""

    block_key = "mamba"
    float32_params = ("a_log", "dt_bias", "d_skip")

    def __init__(self, n_heads: int, head_dim: int, n_groups: int,
                 state_dim: int, conv_kernel: int = 4, chunk: int = 128,
                 time_step=(0.001, 0.1, 1e-4), out_scale: float = 1.0,
                 epsilon: float = 1e-5, input_shape=None, name=None):
        super().__init__(input_shape, name or unique_name("mamba2"))
        if n_heads % n_groups:
            raise ValueError(f"{n_heads} heads do not share {n_groups} "
                             f"groups evenly")
        self.n_heads, self.head_dim = int(n_heads), int(head_dim)
        self.n_groups, self.state_dim = int(n_groups), int(state_dim)
        self.conv_kernel, self.chunk = int(conv_kernel), int(chunk)
        self.time_step = tuple(float(t) for t in time_step)
        self.out_scale, self.epsilon = float(out_scale), epsilon

    @property
    def inner(self) -> int:
        """The heads' width together, H x P: of x, z and the output's input."""
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        """The channels the convolution runs over: x, B and C."""
        return self.inner + 2 * self.n_groups * self.state_dim

    def kept_residuals(self) -> Tuple[str, ...]:
        """Nothing: the whole half is recomputed, the scan included."""
        return ()

    def build(self, input_shape: Shape):
        d, h = input_shape[-1], self.n_heads
        bound = self.conv_kernel ** -0.5
        self.add_weight("w_in", (d, self.inner + self.conv_dim + h),
                        DECODER_INIT)
        self.add_weight("conv_taps", (self.conv_dim, self.conv_kernel),
                        _uniform_init(bound))
        self.add_weight("conv_bias", (self.conv_dim,), _uniform_init(bound))
        self.add_weight("dt_bias", (h,), _dt_bias_init(*self.time_step))
        self.add_weight("a_log", (h,), _a_log_init)
        self.add_weight("d_skip", (h,), "ones")
        self.add_weight("norm", (self.inner,), "ones")
        scale = self.out_scale

        def out_init(key, shape, dtype=jnp.float32):
            return DECODER_INIT(key, shape, dtype) * scale

        self.add_weight("w_out", (self.inner, d), out_init)

    def call(self, params, x, **kw):
        b, s, _ = x.shape
        h, p, g, n = self.n_heads, self.head_dim, self.n_groups, self.state_dim
        f32 = jnp.float32
        with jax.named_scope("ssm.proj_in"):
            z, xbc, dl = jnp.split(x @ params["w_in"],
                                   [self.inner, self.inner + self.conv_dim],
                                   axis=-1)
            padded = jnp.pad(xbc, ((0, 0), (self.conv_kernel - 1, 0), (0, 0)))
            taps = params["conv_taps"].astype(f32)
            conv = sum(taps[:, j] * padded[:, j:j + s].astype(f32)
                       for j in range(self.conv_kernel))
            xbc = jax.nn.silu(conv + params["conv_bias"].astype(f32)).astype(
                x.dtype)
            xs, bs, cs = jnp.split(xbc, [self.inner, self.inner + g * n],
                                   axis=-1)
            dt = jax.nn.softplus(dl.astype(f32) + params["dt_bias"])
        with jax.named_scope("ssm.scan"):
            y = chunked_scan(xs.reshape(b, s, h, p), dt,
                             -jnp.exp(params["a_log"]),
                             bs.reshape(b, s, g, n), cs.reshape(b, s, g, n),
                             params["d_skip"], self.chunk)
        with jax.named_scope("ssm.proj_out"):
            gated = (y.reshape(b, s, self.inner).astype(f32)
                     * jax.nn.silu(z.astype(f32)))
            y = rms_norm(gated.reshape(b, s, g, -1),
                         params["norm"].reshape(g, -1), self.epsilon)
            return y.reshape(b, s, self.inner).astype(x.dtype) @ params["w_out"]


class DecoderBlock(KerasLayer):
    """One decoder layer (see the module docstring). ``attn``: the token
    mixer, a ``GroupedQueryAttention``, a ``LatentAttention``, a
    ``GatedShortConv`` or a ``Mamba2Mixer``; ``mlp``: a
    built-or-unbuilt ``SwiGLU`` or ``SparseMoE``. Either may be None, not
    both: the block is then the other half alone with that half's norms
    (``h += mixer(in_norm(h))`` or ``h += mlp(pre_mlp_norm(h))`` under
    ``"pre"``). ``norms``: ``"sandwich"``,
    a norm before and after each half, or ``"pre"``, the two before alone.
    ``dtype``: the compute type its weights and input are cast to inside the
    block. ``remat``: rematerialise each half in the backward pass (a layer's
    activations then live only while its gradient is computed). What the
    mixer's half keeps is the mixer's to say (``kept_residuals``): of
    attention what only the flash kernel can make, its output and
    log-sum-exp (136 MB a layer at 16 384 tokens of 32 heads x 128), so the
    norm, the projections and rotary are recomputed, not the kernel; of a
    short convolution nothing, the half is recomputed whole. Parameters
    nest: ``{"attn" | "conv": ..., "mlp": ..., "<norm>": {"gain": ...}}``
    (the mixer under its ``block_key``). A block with an expert layer carries
    that layer's state (the router's selection bias and the step's tokens an
    expert) and returns it updated when training. Scopes: the casts of a
    half's weights and of the block's input under ``block.cast``, the norms
    and the two residual adds under ``block.norm``, a dense ``SwiGLU`` under
    ``mlp.dense``; the mixer and the expert layer under their own, never
    inside these (their backward and rematerialised passes keep the name)."""

    NORMS = ("in_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")
    LAYOUTS = {"sandwich": NORMS, "pre": ("in_norm", "pre_mlp_norm")}
    MIXER_NORMS = ("in_norm", "post_attn_norm")

    def __init__(self, attn: KerasLayer, mlp: KerasLayer,
                 epsilon: float = 1e-5, dtype: Optional[str] = "bfloat16",
                 remat: bool = True, norms: str = "sandwich",
                 input_shape=None, name=None):
        super().__init__(input_shape, name or unique_name("decoder_block"))
        if norms not in self.LAYOUTS:
            raise ValueError(f"unknown norm layout {norms!r}; known: "
                             f"{sorted(self.LAYOUTS)}")
        if attn is None and mlp is None:
            raise ValueError("a block with neither a mixer nor a feed-forward")
        self.mixer, self.mlp, self.norms = attn, mlp, norms
        # the attention mixer under the name it has always had; None on a
        # layer that has none
        self.attn = (attn if isinstance(
            attn, (GroupedQueryAttention, LatentAttention)) else None)
        self.epsilon, self.remat = epsilon, remat
        self.dtype = None if dtype is None else jnp.dtype(dtype)
        self.has_state = bool(getattr(mlp, "has_state", False))

    def build(self, input_shape: Shape):
        names = self.LAYOUTS[self.norms]
        for part, norms in ((self.mixer, self.MIXER_NORMS),
                            (self.mlp, ("pre_mlp_norm", "post_mlp_norm"))):
            if part is None:
                names = [n for n in names if n not in norms]
            else:
                part.ensure_built(input_shape)
        self._norms = {n: RMSNorm(self.epsilon, name=f"{self.name}_{n}")
                       for n in names}
        for norm in self._norms.values():
            norm.ensure_built(input_shape)

    def _parts(self):
        parts = {} if self.mixer is None else {self.mixer.block_key: self.mixer}
        if self.mlp is not None:
            parts["mlp"] = self.mlp
        return {**parts, **self._norms}

    def init_params(self, rng):
        return {key: part.init_params(jax.random.fold_in(rng, i))
                for i, (key, part) in enumerate(self._parts().items())}

    def param_pspecs(self):
        return {key: part.param_pspecs() for key, part in self._parts().items()}

    def init_state(self):
        return self.mlp.init_state() if self.has_state else {}

    def call(self, params, x, state=None, training=False, **kw):
        eps = self.epsilon
        mixer_key = None if self.mixer is None else self.mixer.block_key

        def after(p, name, y):
            return rms_norm(y, p[name]["gain"], eps) if name in p else y

        def mixer_half(p, h):
            wide = getattr(self.mixer, "float32_params", ())
            raw = p[mixer_key]
            with jax.named_scope("block.cast"):
                p = _cast(p, self.dtype)
            if wide:          # the mixer's float32 weights, left as they are
                p[mixer_key] = dict(p[mixer_key],
                                    **{k: raw[k] for k in wide})
            with jax.named_scope("block.norm"):
                n = rms_norm(h, p["in_norm"]["gain"], eps)
            a = self.mixer.call(p[mixer_key], n)
            with jax.named_scope("block.norm"):
                return h + after(p, "post_attn_norm", a)

        def mlp_half(p, h, st):
            with jax.named_scope("block.cast"):
                p = _cast(p, self.dtype)
            with jax.named_scope("block.norm"):
                m = rms_norm(h, p["pre_mlp_norm"]["gain"], eps)
            if self.has_state:
                y, st = self.mlp.call(p["mlp"], m, state=st, training=training)
            else:
                with jax.named_scope("mlp.dense"):
                    y = self.mlp.call(p["mlp"], m)
            with jax.named_scope("block.norm"):
                return h + after(p, "post_mlp_norm", y), st

        # What the mixer's half keeps is in the class's docstring. The
        # other half keeps nothing: its forward pass, the expert layer's
        # compacted pass included, runs twice (overflow chunks, rematerialised
        # one by one inside, three times).
        if self.remat:
            if self.mixer is not None:
                kept = self.mixer.kept_residuals()
                mixer_half = jax.checkpoint(
                    mixer_half,
                    policy=jax.checkpoint_policies.save_only_these_names(*kept)
                    if kept else None)
            mlp_half = jax.checkpoint(mlp_half)
        if self.dtype is not None:
            with jax.named_scope("block.cast"):
                x = x.astype(self.dtype)
        mixer_keys = (mixer_key,) + self.MIXER_NORMS
        h, new_state = x, None
        if self.mixer is not None:
            h = mixer_half({k: params[k] for k in mixer_keys if k in params}, h)
        if self.mlp is not None:
            h, new_state = mlp_half(
                {k: v for k, v in params.items() if k not in mixer_keys}, h,
                state if self.has_state else None)
        return (h, new_state) if self.has_state else h
