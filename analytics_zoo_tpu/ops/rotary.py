"""What a rotary position embedding turns by: one spec for both of its paths,
``keras/layers/decoder.py`` ``rotary_embedding`` and the tables of
``ops/qk_rotary.py``'s fused kernels.

``Rotary(theta)`` is the plain embedding (Su et al. 2021): frequency i of a
head ``head_dim`` wide turns by ``r_i = 1 / theta^(2i / head_dim)`` a
position. ``Rotary(theta, Yarn(...))`` is YaRN (Peng et al. 2023), the
frequencies of a model trained on ``original`` positions stretched by
``factor`` where a frequency turns less than ``beta_slow`` times over them,
kept where it turns more than ``beta_fast`` times, and blended between:

    low  = floor(d ln(original / (beta_fast 2 pi)) / (2 ln theta))
    high = ceil(d ln(original / (beta_slow 2 pi)) / (2 ln theta))
    e_i  = 1 - clamp((i - low) / (high - low), 0, 1)
    inv_i = r_i / factor * (1 - e_i) + r_i * e_i

and cos and sin both times ``attention_factor`` (0.1 ln factor + 1 where not
given), so every score between two rotated vectors is scaled by its square.
A spec is frozen and hashable: the fused kernels take it as a static
argument."""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple, Union

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's section of a rotary spec, under the names of a published
    ``rope_parameters`` entry: ``factor``, ``original``
    (``original_max_position_embeddings``), ``beta_fast``, ``beta_slow``,
    ``attention_factor`` (None: 0.1 ln ``factor`` + 1)."""
    factor: float
    original: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None

    def magnitude(self) -> float:
        """What cos and sin are multiplied by."""
        if self.attention_factor is not None:
            return float(self.attention_factor)
        return 0.1 * math.log(self.factor) + 1.0 if self.factor > 1 else 1.0

    def ramp(self, theta: float, head_dim: int) -> Tuple[float, float]:
        """(low, high): the frequencies below ``low`` are kept, those from
        ``high`` on stretched whole."""
        def at(turns):
            return (head_dim * math.log(self.original / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(at(self.beta_fast)), 0)
        high = min(math.ceil(at(self.beta_slow)), head_dim - 1)
        return float(low), float(high if high != low else high + 0.001)


@dataclasses.dataclass(frozen=True)
class Rotary:
    """A rotary embedding: ``theta``, and YaRN's section where the
    frequencies are scaled."""
    theta: float = 10000.0
    yarn: Optional[Yarn] = None

    @property
    def scaled(self) -> bool:
        """Whether the frequencies are YaRN's."""
        return self.yarn is not None


def as_rotary(spec: Union[None, float, Rotary]) -> Optional[Rotary]:
    """A spec from what a layer was given: None (no rotary), a theta (the
    plain embedding) or a spec."""
    if spec is None or isinstance(spec, Rotary):
        return spec
    return Rotary(float(spec))


def frequencies(spec: Rotary, head_dim: int):
    """(inv (head_dim / 2,) float32, magnitude): the turn of each pair a
    position, and what cos and sin are multiplied by (1.0 unscaled)."""
    inv = 1.0 / (spec.theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                                / head_dim))
    if spec.yarn is None:
        return inv, 1.0
    low, high = spec.yarn.ramp(spec.theta, head_dim)
    pair = jnp.arange(head_dim // 2, dtype=jnp.float32)
    kept = 1.0 - jnp.clip((pair - low) / (high - low), 0.0, 1.0)
    inv = inv / spec.yarn.factor * (1.0 - kept) + inv * kept
    return inv, spec.yarn.magnitude()


def angles(spec: Rotary, seq: int, head_dim: int):
    """(cos, sin), each ``(seq, head_dim / 2)`` float32 and times the spec's
    magnitude: the angle of pair i at position t is ``t * inv_i``."""
    inv, magnitude = frequencies(spec, head_dim)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if magnitude != 1.0:
        cos, sin = cos * magnitude, sin * magnitude
    return cos, sin
