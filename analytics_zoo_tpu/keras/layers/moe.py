"""MoE as layers — expert feed-forwards in the standard layer library:
``MoE`` (top-1, capacity, Switch) and ``SparseMoE`` (sigmoid or softmax
top-k, nothing dropped, shared experts, a share of the experts held, a
balance term for the loss: today's sparse decoders).

Wraps :mod:`analytics_zoo_tpu.parallel.moe` (top-1 dispatch/combine, the
Mesh-TF/Switch formulation) as a KerasLayer with a residual connection, so
``Sequential``/functional models get sparse-expert capacity through the
same compile/fit path as everything else. Expert weights carry an
``("expert",)`` leading-axis partition spec: on a mesh with an ``expert``
axis GSPMD shards the expert matmuls and inserts the dispatch/combine
collectives automatically.

The reference has no MoE (SURVEY.md §2.4) — beyond-parity, like the
ring-attention module.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from analytics_zoo_tpu.keras.engine.base import (
    KerasLayer, Regularizer, Shape, normal_init, unique_name,
)


class MoE(KerasLayer):
    """Residual top-1 mixture-of-experts FFN over the last dim.

    Input (..., d) -> output (..., d): ``x + moe_ffn(norm-free)(x)`` —
    dropped (over-capacity) tokens pass through on the residual, the
    standard Switch behavior.
    """

    def __init__(self, n_experts: int, hidden_dim: int,
                 capacity_factor: float = 1.25, router_l2: float = 0.0,
                 expert_axis: str = "model", input_shape=None, name=None):
        """``expert_axis``: mesh axis the expert leading dim shards over —
        "model" by default (on the standard (data, model) mesh the TP axis
        doubles as the expert axis); use "expert" on a dedicated-EP mesh,
        or None to keep experts replicated. ``router_l2``: plain L2 on the
        router weights (NOT the Switch load-balancing aux loss — that needs
        the routing statistics; compute it with parallel.moe.moe_ffn(...,
        return_aux=True) and add it to the training loss directly)."""
        super().__init__(input_shape, name or unique_name("moe"))
        self.n_experts = int(n_experts)
        self.hidden_dim = int(hidden_dim)
        self.capacity_factor = float(capacity_factor)
        self.router_l2 = float(router_l2)
        self.expert_axis = expert_axis

    def build(self, input_shape: Shape):
        d = input_shape[-1]
        ps = (self.expert_axis, None, None) if self.expert_axis else None

        # per-matrix He fans (the generic _fans would fold n_experts into
        # the receptive field and under-scale by sqrt(E))
        def expert_init(fan):
            def init(key, shape, dtype=jnp.float32):
                return math.sqrt(2.0 / fan) * jax.random.normal(
                    key, shape, dtype)
            return init

        self.add_weight(
            "router", (d, self.n_experts), init="normal",
            regularizer=Regularizer(l2=self.router_l2) if self.router_l2
            else None)
        self.add_weight("w_in", (self.n_experts, d, self.hidden_dim),
                        init=expert_init(d), pspec=ps)
        self.add_weight("w_out", (self.n_experts, self.hidden_dim, d),
                        init=expert_init(self.hidden_dim), pspec=ps)

    def call(self, params, x, **kw):
        from analytics_zoo_tpu.parallel.moe import moe_ffn

        shape = x.shape
        flat = x.reshape(-1, shape[-1])
        y = moe_ffn({"router": params["router"], "w_in": params["w_in"],
                     "w_out": params["w_out"]}, flat,
                    capacity_factor=self.capacity_factor)
        return x + y.reshape(shape)


# initial weights of the decoder layers (here and in decoder.py): normal(0,
# 0.02), the `initializer_range` the published decoders state
DECODER_INIT = normal_init(0.02)


class SparseMoE(KerasLayer):
    """Top-k expert layer that drops nothing, with shared experts and a
    selection bias or a balance term, over the experts this chip holds.

    ``y = FF_shared(x) + sum over a token's k picks e of w_e FF_e(x)``, each
    ``FF`` a SwiGLU (``activation="swiglu"``: ``W_down(silu(W_gate x) * W_up
    x)``, gate | up in one kernel) or a squared-ReLU feed-forward
    (``"relu2"``: ``W_down relu(W_up x)^2``, an up kernel alone):
    ``s = sigmoid(W_r x)`` in float32 over all ``n_experts``; the ``top_k``
    largest of ``s + b`` are picked (``b``, the selection bias, is state, not
    a weight: it steers the pick and gets no gradient); ``w = s[picked]``,
    normalised to sum 1 (``route_norm``: over the sum plus ``route_eps``) and
    scaled by ``route_scale``. ``scores="softmax"``: ``s = softmax(W_r x)``
    over all ``n_experts`` and the ``top_k`` most probable picked, ``b`` left
    out (``parallel/moe.py`` ``route_topk``). ``n_shared=0``: no shared
    expert, everything the layer gives a token comes from the experts held;
    else one shared feed-forward ``shared_width`` wide (``width * n_shared``
    where not given).

    ``experts_held = (offset, count)``: of the ``n_experts`` the router
    scores, this layer holds weights for ``count``, numbered from ``offset``
    — one chip's share of an expert-parallel group. It routes over all of
    them and computes the part of the sum its own experts give; on one chip
    no exchange is made and the absent experts' part is left out. Default:
    all of them. No capacity: shapes are fixed whatever the routing
    (parallel/moe.py ``held_experts_ffn``): the held rows go through one
    compacted pass where they fit its buffer (twice the uniform share), else
    in chunks, and always in chunks where nothing steers the load
    (``compact``).

    State, returned updated from a training call as batch-norm statistics
    are: ``select_bias`` (n_experts,), after a step
    ``b += bias_rate * sign(mean(n) - n_e)`` then centred, ``n_e`` the
    step's tokens routed to expert e (the auxiliary-loss-free balancing of
    Wang et al. 2024); ``expert_tokens`` (n_experts,), that ``n`` itself, and
    ``compact`` (), 1 where the step's held assignments went through in one
    compacted pass and 0 where they overflowed it, no such pass exists or
    the layer takes none (``held_experts_ffn``): ``Estimator.train`` hands both to the counters
    with the loss.

    ``balance_coef`` > 0 (softmax scores): a training call also leaves the
    statistics of the balance term of Switch / Mixtral training in its state,
    over all ``n_experts``: ``balance_f``, the tokens that picked each expert
    over the tokens (``counts / T``, summing to ``top_k``), and
    ``balance_p``, each expert's mean probability (which carries the
    gradient), under the scope ``moe.balance``. The model pools them over
    its expert layers into ``balance_coef * n_experts * sum(f * p)``
    (``models/causal_lm.py`` ``CausalLM.auxiliary_loss``).
    """

    has_state = True

    def __init__(self, n_experts: int, width: int, top_k: int,
                 experts_held=None, n_shared: int = 1,
                 route_norm: bool = True, route_scale: float = 1.0,
                 bias_rate: float = 0.001, route_eps: float = 1e-20,
                 activation: str = "swiglu", shared_width=None,
                 scores: str = "sigmoid", balance_coef: float = 0.0,
                 input_shape=None, name=None):
        from analytics_zoo_tpu.parallel.moe import ACTIVATIONS

        super().__init__(input_shape, name or unique_name("sparse_moe"))
        if activation not in ACTIVATIONS:
            raise ValueError(f"unknown expert activation {activation!r}; "
                             f"known: {sorted(ACTIVATIONS)}")
        self.n_experts, self.width, self.top_k = int(n_experts), int(width), int(top_k)
        offset, count = experts_held or (0, self.n_experts)
        if not (0 <= offset and count >= 1 and offset + count <= self.n_experts):
            raise ValueError(f"experts_held {(offset, count)} is not a range "
                             f"of the {self.n_experts} experts")
        self.experts_held = (int(offset), int(count))
        self.n_shared = int(n_shared)
        self.shared_width = int(shared_width or self.width * self.n_shared)
        self.activation = activation
        self.route_norm, self.route_scale = route_norm, float(route_scale)
        self.bias_rate, self.route_eps = float(bias_rate), float(route_eps)
        if balance_coef and scores != "softmax":
            raise ValueError("a balance term over sigmoid scores: only the "
                             "softmax router's is built")
        self.scores, self.balance_coef = scores, float(balance_coef)

    @property
    def compact(self) -> bool:
        """Whether the held rows may take the one compacted pass: where a
        selection bias steers the load toward the uniform share. A softmax
        router has none (its picks leave the bias out), and in training on
        one chip its share on the experts held wanders back and forth across
        the buffer's edge, each crossing costing the difference between the
        two paths; the chunks' cost follows the load with no edge."""
        return self.scores == "sigmoid" and self.bias_rate > 0

    def build(self, input_shape: Shape):
        d, init = input_shape[-1], DECODER_INIT
        count = self.experts_held[1]
        # the first kernel: gate | up side by side, or up alone
        first, wide = (("w_gate_up", 2) if self.activation == "swiglu"
                       else ("w_up", 1))
        self.add_weight("router", (d, self.n_experts), init)
        if self.n_shared:
            self.add_weight("shared_" + first, (d, wide * self.shared_width),
                            init)
            self.add_weight("shared_w_down", (self.shared_width, d), init)
        self.add_weight("experts_" + first, (count, d, wide * self.width),
                        init)
        self.add_weight("experts_w_down", (count, self.width, d), init)
        self.add_state("select_bias", (self.n_experts,), "zeros")
        self.add_state("expert_tokens", (self.n_experts,), "zeros")
        self.add_state("compact", (), "zeros")
        if self.balance_coef:
            self.add_state("balance_f", (self.n_experts,), "zeros")
            self.add_state("balance_p", (self.n_experts,), "zeros")

    def call(self, params, x, state=None, training=False, **kw):
        from analytics_zoo_tpu.parallel.moe import (
            ACTIVATIONS, held_experts_ffn, route_topk)

        state = state or self.init_state()
        act = ACTIVATIONS[self.activation]
        first = "w_gate_up" if self.activation == "swiglu" else "w_up"
        shape = x.shape
        flat = x.reshape(-1, shape[-1])
        with jax.named_scope("moe.route"):
            routed = route_topk(
                flat, params["router"], state["select_bias"], self.top_k,
                self.route_norm, self.route_scale, self.route_eps, self.scores)
            picked, weights, counts = routed[:3]
        with jax.named_scope("moe.experts"):
            y, compact = held_experts_ffn(
                flat, picked, weights, params["experts_" + first],
                params["experts_w_down"], self.n_experts,
                self.experts_held[0], self.activation, self.compact)
        if self.n_shared:
            with jax.named_scope("moe.shared"):
                y = y + act(flat @ params["shared_" + first]) @ params[
                    "shared_w_down"]
        if training:
            bias = state["select_bias"] + self.bias_rate * jnp.sign(
                jnp.mean(counts) - counts)
            state = {"select_bias": bias - jnp.mean(bias),
                     "expert_tokens": counts,
                     "compact": compact.astype(jnp.float32)}
            if self.balance_coef:
                with jax.named_scope("moe.balance"):
                    state.update(balance_f=counts / flat.shape[0],
                                 balance_p=routed[3])
        return y.reshape(shape), state
