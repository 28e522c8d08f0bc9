"""Fused MoE Experts op: gate -> top-k dispatch -> expert FFN -> combine.

TPU-native fusion of the reference's MoE subgraph (topk + group_by +
per-expert Linear pairs + aggregate, model.h:507-512 `FFModel::moe` and
examples/cpp/mixture_of_experts/moe.cc:42-53): under SPMD the per-expert
ops cannot live on different devices, so the experts become one op with
stacked weights [E, ...] whose leading dim is sharded over the 'expert'
mesh axis — the placement the reference's search assigns per-op
(moe.cc:65-83) becomes a sharding choice on this node. Dispatch runs as
einsums on replicated routing tensors; with an expert axis the token
exchange is an explicit reduce-scatter/all-gather pair inside shard_map
(parallel/expert.py).

The load-balance auxiliary loss uses the FULL top-k assignment (every
selected expert counts toward the token fraction), matching the reference's
Aggregate backward which accumulates over all k slots (src/ops/aggregate.cu
agg_backward_kernel loops k).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.initializers import DefaultWeightInitializer
from flexflow_tpu.ops.base import (DimRole, Op, OpContext, register_op,
                                   scoped)
from flexflow_tpu.ops.moe import (combine_rows, expert_capacity,
                                  gmm_row_tile, grouped_matmul,
                                  grouped_products_walk, load_balance_loss,
                                  make_dispatch_tensors, route_held_experts,
                                  route_scores, rows_from_tokens,
                                  sums_rows_by_kernel)


@register_op(OperatorType.EXPERTS)
class Experts(Op):
    """inputs: (x [B, D], gate [B, E] router probabilities) -> [B, D]."""

    def __init__(self, layer, input_shapes):
        p = layer.properties
        self.n_experts = p["n"]
        self.k = p.get("k", 1)
        self.hidden_size = p["hidden_size"]
        self.alpha = p.get("alpha", 2.0)
        self.lambda_bal = p.get("lambda_bal", 0.0)
        # mesh axis experts are sharded over; set by the search when it
        # picks an "_ep" choice (or by the user at build time)
        self.expert_parallel = p.get("expert_parallel", None)
        self.kernel_init = p.get("kernel_initializer") or DefaultWeightInitializer()
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        b, d = self.input_shapes[0]
        return [(b, d)]

    def init_params(self, rng):
        e = self.n_experts
        d = self.input_shapes[0][-1]
        h = self.hidden_size
        ks = jax.random.split(rng, 2)
        return {
            "w_h": self.kernel_init(ks[0], (e, d, h)),
            "b_h": jnp.zeros((e, h)),
            "w_o": self.kernel_init(ks[1], (e, h, d)),
            "b_o": jnp.zeros((e, d)),
        }

    def forward(self, params, inputs, ctx: OpContext):
        x, gate = inputs
        b = x.shape[0]
        values, assign = jax.lax.top_k(gate, self.k)
        cap = expert_capacity(b, self.k, self.n_experts, self.alpha)
        dispatch, combine = make_dispatch_tensors(
            assign, values.astype(jnp.float32), self.n_experts, cap)

        from flexflow_tpu.parallel.expert import (_mesh_axes, dense_moe_ffn,
                                                  expert_parallel_ffn)

        axis = self.expert_parallel
        mesh_axes = _mesh_axes(ctx.mesh) if ctx.mesh is not None else {}
        if axis and mesh_axes.get(axis, 1) > 1:
            y = expert_parallel_ffn(
                x, dispatch, combine, params["w_h"], params["b_h"],
                params["w_o"], params["b_o"], ctx.mesh, expert_axis=axis)
        else:
            y = dense_moe_ffn(x, dispatch, combine, params["w_h"],
                              params["b_h"], params["w_o"], params["b_o"])

        if self.lambda_bal > 0.0:
            self._aux_loss = load_balance_loss(assign, gate, self.n_experts,
                                               self.lambda_bal)
        return [y]

    def output_dim_roles(self):
        return [(DimRole.SAMPLE, DimRole.CHANNEL)]

    def flops(self):
        b, d = self.input_shapes[0]
        cap = expert_capacity(b, self.k, self.n_experts, self.alpha)
        e, h = self.n_experts, self.hidden_size
        ffn = 2 * e * cap * d * h * 2
        route = 2 * b * self.k * e * cap * d * 2  # dispatch + combine einsums
        return ffn + route

    def params_elems(self):
        e, h = self.n_experts, self.hidden_size
        d = self.input_shapes[0][-1]
        return e * (d * h + h + h * d + d)


def squared_relu(x):
    return jnp.square(jax.nn.relu(x))


def buffer_rows(pairs: int, held: int, n_experts: int, slack: float) -> int:
    """Rows of a layer's one local buffer: every pair if all experts are
    held, else the expected held pairs with the slack; in 128s, and then
    in the row tile the grouped products walk such a buffer in
    (`moe.gmm_row_tile`: at most 128 rows more, never fewer)."""
    rows = pairs if held == n_experts else min(
        pairs, int(pairs * held / n_experts * (1.0 + slack)) + 1)
    rows = -(-rows // 128) * 128
    tile = gmm_row_tile(rows, held)
    return -(-rows // tile) * tile


# what the gate's product goes through in the gated form
GATE_ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


@register_op(OperatorType.MOE_LAYER)
class MoELayer(Op):
    """A mixture-of-experts feed-forward layer over the experts HELD here:
    input [B, S, D] -> [B, S, D].

        s = sigmoid(x W_r)            float32, over all `n_experts`
        top-k of s + b; w_j = s_j / (sum of the k + 1e-20) * routed_scaling
        expert_j(x) = relu(x U_j)^2 D_j        (no gate, no bias)
        out = sum over the chosen j that are held of w_j expert_j(x)
              + shared(x)             (the experts' own form, `shared_width`
                                       wide: with `gated` a third leaf
                                       `ws_gate`, PR 39)

    Three things a model may state otherwise (PR 31), each a property:
    `scoring` "softmax": the k largest LOGITS x W_r are chosen and w is
    the softmax over them (no bias leaf; with `norm_topk` that is the
    softmax over all `n_experts` renormalised over the chosen); `gated`:
    expert_j(x) = (act(x G_j) * (x U_j)) D_j with a third leaf `w_gate`,
    and `activation` names act: "relu" or (PR 34) "silu"; a second
    input [B, S, D] that the ROUTER reads in place of x (a model that
    routes from the pre-attention norm, so that the experts' choice is
    known while attention runs): the experts still transform the first.

    The layer is told which experts it holds (`experts_held` of them from
    `expert_offset`); it routes over all `n_experts` and computes its own
    experts' part. A (token, slot) pair routed to an expert that is not
    held contributes nothing: the chip that holds it adds that part. On
    one chip the layer runs without an exchange, and nothing here stands
    in for one.

    Routing is dropless for the held experts: the pairs are sorted by
    expert into ONE buffer whose rows are the expected number of held
    pairs, tokens * k * held / n_experts, times (1 + `slot_slack`), in
    whole row tiles of the grouped matrix product that runs over the
    buffer (`buffer_rows`; PR 53). There is no per-expert
    capacity. Pairs beyond the buffer are counted (`moe/overflow_slots`,
    with `moe/slots_held` and `moe/load_max_over_mean` beside it: they
    leave the step with the metrics and are read once an epoch).

    Rows and tokens (PR 32): the routing sort is kept in both directions
    (`route_held_experts`: `slot` row -> pair, `row_of_pair` pair -> row).
    A row reads its token (`rows_from_tokens`) and a token reads its k
    rows and adds them, weighted, in float32 (`combine_rows`); each has a
    `custom_vjp` whose backward is the other direction's gather
    (`tokens_from_rows` for the first, a row gather of dY for the
    second), because the transpose autodiff picks for a gather is a
    scatter-add, which this chip runs row by row at 0.43 us a row: two of
    them took 63 of a 226 ms step (ops/moe.py). Both calls run under the
    nested scope `moe_combine`. Where the layer holds a small share of
    the experts, on the TPU, `tokens_from_rows` reads the buffer's rows
    once, in token order, and a kernel adds them (PR 37:
    `moe.sums_rows_by_kernel`, from the static shapes alone); there the
    combine's backward is that kernel's transpose, one kernel more, in
    place of the row gather of dY (PR 49).

    Weights: w_router [D, n_experts], e_bias [n_experts] (b, the
    score-correction bias, sigmoid scoring only: it enters the choice
    only, so its gradient is exactly zero and no optimizer moves it;
    models that balance their experts without an auxiliary loss adjust it
    outside the gradient), w_up [held, D, F], w_down [held, F, D], with
    `gated` w_gate [held, D, F], and with a shared expert ws_up [D, Fs],
    ws_down [Fs, D] (and with `gated` ws_gate [D, Fs]). The router's
    leaves stay float32 in the compute copy: a bias rounded to bfloat16
    moves the choice of every token alike.
    """

    scopes_itself = "moe_layer"

    full_precision_params = ("w_router", "e_bias")

    def __init__(self, layer, input_shapes):
        p = layer.properties
        self.n_experts = p["n_experts"]
        self.experts_held = p.get("experts_held") or self.n_experts
        self.expert_offset = p.get("expert_offset", 0)
        self.k = p["k"]
        self.hidden_size = p["hidden_size"]
        self.shared_width = p.get("shared_width", 0)
        self.routed_scaling = p.get("routed_scaling", 1.0)
        self.norm_topk = p.get("norm_topk", True)
        self.slot_slack = p.get("slot_slack", 0.5)
        self.scoring = p.get("scoring", "sigmoid")
        self.gated = p.get("gated", False)
        self.activation = p.get("activation", "relu")
        # the shared expert's output times sigmoid(x w_shared_gate), one
        # scalar a position (PR 58)
        self.shared_gate = bool(p.get("shared_gate", False))
        if self.shared_gate and not p.get("shared_width", 0):
            raise ValueError(f"moe_layer '{layer.name}': shared_gate "
                             f"without a shared expert")
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"moe_layer '{layer.name}': unknown scoring "
                             f"{self.scoring!r}")
        if self.activation not in GATE_ACTIVATIONS or (
                self.activation != "relu" and not self.gated):
            raise ValueError(
                f"moe_layer '{layer.name}': activation {self.activation!r} "
                f"(the gated form knows {sorted(GATE_ACTIVATIONS)}; the "
                f"ungated form is relu squared)")
        if self.expert_offset + self.experts_held > self.n_experts:
            raise ValueError(
                f"moe_layer '{layer.name}': experts {self.expert_offset}.."
                f"{self.expert_offset + self.experts_held} held of "
                f"{self.n_experts}")
        self.kernel_init = (p.get("kernel_initializer")
                            or DefaultWeightInitializer())
        self._counters = None
        # whether the forward, as last traced, summed rows by the kernel
        self._sum_rows = False
        self._spread_rows = False
        # the row tile and the products whose weights stay resident, of
        # the grouped products as last traced (0: none, or not by kernel)
        self._row_tile = self._resident_products = 0
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        return [tuple(self.input_shapes[0])]

    @property
    def matrices(self) -> int:
        """Matrices an expert has: up and down, and with `gated` a gate."""
        return 3 if self.gated else 2

    @property
    def tokens(self):
        b, s, _ = self.input_shapes[0]
        return b * s

    @property
    def buffer_rows(self):
        """Rows of the layer's one local buffer (`buffer_rows` above)."""
        return buffer_rows(self.tokens * self.k, self.experts_held,
                           self.n_experts, self.slot_slack)

    def init_params(self, rng):
        d = self.input_shapes[0][-1]
        e, f = self.experts_held, self.hidden_size
        ks = jax.random.split(rng, 6)
        params = {
            "w_router": self.kernel_init(ks[0], (d, self.n_experts)),
            "w_up": self.kernel_init(ks[1], (e, d, f)),
            "w_down": self.kernel_init(ks[2], (e, f, d)),
        }
        if self.scoring == "sigmoid":
            params["e_bias"] = jnp.zeros((self.n_experts,))
        if self.gated:
            params["w_gate"] = self.kernel_init(ks[5], (e, d, f))
        if self.shared_width:
            params["ws_up"] = self.kernel_init(ks[3], (d, self.shared_width))
            params["ws_down"] = self.kernel_init(ks[4],
                                                 (self.shared_width, d))
            if self.gated:
                params["ws_gate"] = self.kernel_init(
                    jax.random.fold_in(ks[3], 1), (d, self.shared_width))
            if self.shared_gate:
                params["w_shared_gate"] = self.kernel_init(
                    jax.random.fold_in(ks[3], 2), (d, 1))
        return params

    def forward(self, params, inputs, ctx: OpContext):
        x, x_router = inputs[0], inputs[-1]   # one input: the same
        cd = ctx.compute_dtype
        b, s, d = x.shape
        rows = self.buffer_rows

        def route(params, xr):
            logits = jnp.dot(xr.astype(jnp.float32),
                             params["w_router"].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            if self.scoring == "sigmoid":
                logits = jax.nn.sigmoid(logits)
                bias = params["e_bias"].astype(jnp.float32)
            else:
                bias = None
            weights, experts = route_scores(
                logits, bias, self.k, self.norm_topk, self.routed_scaling,
                self.scoring)
            return weights, route_held_experts(
                experts, self.experts_held, self.expert_offset, rows)

        def dispatch(xt, r):
            return rows_from_tokens(xt, r).astype(cd)

        def experts_held(params, x_buf, group_sizes):
            h = grouped_matmul(x_buf, params["w_up"].astype(cd), group_sizes)
            if self.gated:
                g = grouped_matmul(x_buf, params["w_gate"].astype(cd),
                                   group_sizes)
                h = (GATE_ACTIVATIONS[self.activation](
                    g.astype(jnp.float32))
                     * h.astype(jnp.float32)).astype(cd)
            else:
                h = squared_relu(h.astype(jnp.float32)).astype(cd)
            return grouped_matmul(h, params["w_down"].astype(cd),
                                  group_sizes)

        def combine(o, weights, r):
            return combine_rows(o, weights, r, self._saw_spread_rows)

        def shared(params, xt):
            hs = jnp.dot(xt.astype(cd), params["ws_up"].astype(cd),
                         preferred_element_type=jnp.float32)
            if self.gated:     # the experts' own form (PR 39)
                gs = jnp.dot(xt.astype(cd), params["ws_gate"].astype(cd),
                             preferred_element_type=jnp.float32)
                hs = GATE_ACTIVATIONS[self.activation](gs) * hs
            else:
                hs = squared_relu(hs)
            ys = jnp.dot(hs.astype(cd), params["ws_down"].astype(cd),
                         preferred_element_type=jnp.float32)
            if self.shared_gate:
                ys = ys * jax.nn.sigmoid(jnp.dot(
                    xt.astype(cd), params["w_shared_gate"].astype(cd),
                    preferred_element_type=jnp.float32))
            return ys

        def layer(params, x, x_router):
            xt = x.reshape(b * s, d)
            weights, r = scoped("moe_route", route)(
                params, x_router.reshape(b * s, d))
            x_buf = scoped("moe_combine", dispatch)(xt, r)
            o = scoped("moe_grouped_matmul", experts_held)(
                params, x_buf, r["group_sizes"])
            y = scoped("moe_combine", combine)(o, weights, r)
            if self.shared_width:
                y = y + scoped("moe_shared", shared)(params, xt)
            return y.reshape(b, s, d).astype(x.dtype), r["load"], \
                r["overflow"]

        # for `executor.moe_sum_rows_ops` (this is trace time):
        # `tokens_from_rows`, for the combine and for the dispatch's
        # backward, sums the rows by the kernel
        self._sum_rows = sums_rows_by_kernel(rows, d, b * s, self.k)
        self._row_tile, self._resident_products = grouped_products_walk(
            rows, self.experts_held, d, self.hidden_size, self.matrices)
        y, load, overflow = scoped(self.scopes_itself, layer)(
            params, x, x_router)
        load = load.astype(jnp.float32)
        self._counters = {
            "moe/slots_held": ("sum", jnp.sum(load)),
            "moe/overflow_slots": ("sum", overflow.astype(jnp.float32)),
            "moe/load_max_over_mean": (
                "mean", jnp.max(load) / jnp.maximum(jnp.mean(load), 1.0)),
        }
        return [y]

    def _saw_spread_rows(self):
        self._spread_rows = True

    def traced_gauges(self):
        """`executor.moe_sum_rows_ops`: the forward, as last traced, had
        `tokens_from_rows` add the buffer's rows into their tokens by the
        kernel `moe_sum_rows` (PR 37; 0 where the layer holds all its
        experts, and on the CPU). `executor.moe_spread_rows_ops`: a
        backward of `combine_rows` has been traced, and it took that
        kernel's transpose, `moe_spread_rows` (PR 49; `combine_rows`
        calls `_saw_spread_rows` from there). `executor.moe_row_tile` and
        `executor.moe_resident_weight_products` (PR 53): the row tile the
        layer's grouped products walk the buffer in, and how many of its
        `gmm` products (an expert's matrices forward and their `d lhs`:
        six in a gated layer, four else) contract in ONE tile, their
        group's weight panel fetched once a group
        (`moe.grouped_products_walk`; 0 where `lax.ragged_dot` runs). The
        executor adds the layers' values up: over `executor.expert_ops`
        layers."""
        return {"executor.moe_sum_rows_ops": int(bool(self._sum_rows)),
                "executor.moe_spread_rows_ops": int(self._spread_rows),
                "executor.moe_row_tile": self._row_tile,
                "executor.moe_resident_weight_products":
                    self._resident_products}

    def output_dim_roles(self):
        # routing sorts the tokens of the whole batch into one buffer: the
        # sequence dim is not independently shardable
        return [(DimRole.SAMPLE, DimRole.OTHER, DimRole.CHANNEL)]

    def flops(self):
        """Forward FLOPs of the work done here: the router over all
        experts, the expected held pairs through an expert's two matrices
        (three if gated), the shared expert."""
        d = self.input_shapes[0][-1]
        t = self.tokens
        pairs = t * self.k * self.experts_held / self.n_experts
        return int(2 * t * d * self.n_experts
                   + 2 * self.matrices * pairs * d * self.hidden_size
                   + 2 * self.matrices * t * d * self.shared_width
                   + 2 * t * d * self.shared_gate)

    def interior_bytes(self):
        """Kept for the backward pass besides the output: the buffer's
        rows in and between the products (each first product's result and
        what the second takes), the shared expert's hidden activations,
        the scores."""
        d = self.input_shapes[0][-1]
        return (self.buffer_rows * (d + self.matrices * self.hidden_size)
                + self.tokens * self.matrices * self.shared_width
                ) * self.dtype.size + 4 * self.tokens * self.n_experts

    def params_elems(self):
        d = self.input_shapes[0][-1]
        return ((d + (self.scoring == "sigmoid")) * self.n_experts
                + self.matrices * self.experts_held * d * self.hidden_size
                + self.matrices * d * self.shared_width
                + d * self.shared_gate)
