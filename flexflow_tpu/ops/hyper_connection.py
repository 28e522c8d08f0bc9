"""Manifold-constrained hyper-connections: a residual path of several
mixed streams (mHC, arXiv:2512.24880; new scope vs the reference, whose
every block is x + f(norm(x))).

The residual stream is n copies of the hidden width side by side along
the lanes, X [B, S, n*C] (never a [B, S, n, C] view: n would lie on the
sublanes). A sublayer F (a pre-normed branch, C -> C) is wrapped in two
ops, so that the search prices and places each as one node:

    HC_PRE   X -> (h, maps, X)       h [B, S, C] is what F reads
    HC_POST  (X, y, maps) -> X'      y = F(h)

With x = vec(X) in R^{nC}, the leaves phi_pre, phi_post [nC, n], phi_res
[nC, n^2], b_pre, b_post [n], b_res [n, n], alpha [3] (float32 all), a
position's arithmetic in float32:

    r      = (mean(x^2) + eps)^-1/2            no learned scale
    H_pre  = sigmoid(alpha_0 r (x phi_pre) + b_pre)              [n]
    H_post = 2 sigmoid(alpha_1 r (x phi_post) + b_post)          [n]
    M_0    = exp(clip(alpha_2 r mat(x phi_res) + b_res, lo, hi)) [n, n]
    M_t    = rows(cols(M_{t-1})),  cols(M) = M / (sum_i M[i, j] + eps),
             rows(M) = M / (sum_j M[i, j] + eps),  t = 1..iters
    H_res  = M_iters               (Sinkhorn-Knopp: doubly stochastic)
    h      = sum_i H_pre[i] X[i]
    X'[i]  = sum_j H_res[i, j] X[j] + H_post[i] y

The RMS division is applied to the n (n + 2) products, not to the stream
(the same arithmetic, and the paper's own reordering). ``maps`` is ONE
exported tensor [B, S, 128] float32: lanes 0..n-1 H_pre, n..2n-1 H_post,
2n + i n + j H_res[i, j] (and after them 2 n lanes of a witness: how far
H_res's rows and columns sum from one). HC_PRE hands X through as its third output and
HC_POST reads THAT: the two uses of one stream then meet inside HC_PRE's
backward, which adds HC_POST's cotangent in its one pass, where autodiff
would add two [B, S, n*C] arrays in a pass of its own.

Scopes (`ops.base.scoped`): `hyper_connection` around either op, inside
it `hc_read` (statistic, products, read map and h), `hc_maps` (the other
two maps: sigmoid, the Sinkhorn steps, their `jax.vjp` in the backward,
which forms the steps again from the logits) and `hc_write`. Two bodies,
one mathematics (`by_kernel` picks from the static shapes):
`pallas_kernels.hc_read_lanes` / `hc_maps_lanes` / `hc_write_lanes`, one
pass over the stream each way and the maps' steps as one kernel each way,
where Pallas is on, the shape is whole blocks and the mesh one device;
else the `jax.numpy` forms here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.ops.base import (DimRole, Op, OpContext, register_op,
                                   scoped)

MAP_LANES = 128     # lanes of the exported maps (and of the kernels' zr)
SCOPE = "hyper_connection"
F32 = jnp.float32
# how an op draws its own leaves: alpha, the std of phi, the diagonal of
# b_res, the std of b_pre and b_post, the std of b_res's noise (at 0.3
# twenty Sinkhorn steps leave the sums within 1e-4 of one; at 1 not)
INIT = (0.01, 0.02, 2.0, 1.0, 0.3)
HIGHEST = jax.lax.Precision.HIGHEST


def map_count(n: int) -> int:
    """Columns of the three maps: n + n + n * n."""
    return n * (n + 2)


def read_plain(x, phi, a, b, n: int, eps: float):
    """`pallas_kernels.hc_read_lanes` in `jax.numpy`: (h, zr, x) for x
    [.., n*C], phi [n*C, K] float32, a, b [K]."""
    c, k = x.shape[-1] // n, phi.shape[1]
    xf = x.astype(F32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    z = jnp.dot(xf, phi.astype(F32), precision=HIGHEST)
    pre = jax.nn.sigmoid(a[:n] * r * z[..., :n] + b[:n])
    h = sum(pre[..., i:i + 1] * xf[..., i * c:(i + 1) * c] for i in range(n))
    pad = jnp.zeros(x.shape[:-1] + (MAP_LANES - k - 1,), F32)
    return h.astype(x.dtype), jnp.concatenate([z, pad, r], axis=-1), x


def write_plain(x, y, maps, n: int):
    """`pallas_kernels.hc_write_lanes` in `jax.numpy`."""
    c = y.shape[-1]
    yf = y.astype(F32)
    xs = [x[..., j * c:(j + 1) * c].astype(F32) for j in range(n)]
    return jnp.concatenate([
        maps[..., n + i:n + i + 1] * yf + sum(
            maps[..., 2 * n + i * n + j:2 * n + i * n + j + 1] * xs[j]
            for j in range(n)) for i in range(n)], axis=-1).astype(x.dtype)


def _maps(logits, n, iters, eps, clamp):
    lead, k = logits.shape[:-1], logits.shape[-1]
    # the positions along the lanes: a step is then elementwise work on
    # [n, n, T] and sums over its leading axes
    lt = logits.reshape(-1, k).T
    pre, post = jax.nn.sigmoid(lt[:n]), 2.0 * jax.nn.sigmoid(lt[n:2 * n])
    m = jnp.exp(jnp.clip(lt[2 * n:], *clamp)).reshape(n, n, -1)
    def step(m, _):     # ONE loop body in the program, not `iters` copies
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)     # columns
        return m / (jnp.sum(m, axis=1, keepdims=True) + eps), None     # rows

    m, _ = jax.lax.scan(step, m, None, length=iters)
    return jnp.concatenate([pre, post, m.reshape(n * n, -1)]).T.reshape(
        lead + (k,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def hc_maps(logits, n: int, iters: int, eps: float, clamp):
    """logits [.., K] float32 -> [H_pre ; H_post ; H_res] [.., K]: the
    two sigmoids and ``iters`` Sinkhorn steps (columns, then rows) on
    exp(clip(.)). The backward is the steps' own `jax.vjp`, formed again
    from the logits: no step's result is kept between the passes."""
    return _maps(logits, n, iters, eps, clamp)


def _hc_maps_fwd(logits, n, iters, eps, clamp):
    return _maps(logits, n, iters, eps, clamp), logits


def _hc_maps_bwd(n, iters, eps, clamp, logits, g):
    return jax.vjp(lambda t: _maps(t, n, iters, eps, clamp), logits)[1](g)


hc_maps.defvjp(_hc_maps_fwd, _hc_maps_bwd)


def by_kernel(mesh, shape, n: int) -> bool:
    """Whether a stream of ``shape`` [B, S, n*C] takes the one-pass
    kernels: Pallas on, whole blocks, one device (a bare kernel call has
    no partitioning)."""
    from flexflow_tpu.ops import pallas_kernels as pk
    b, s, width = shape
    return bool(pk.pallas_mode() != "off" and width % n == 0
                and pk.hc_shape_legal(b * s, n, width // n)
                and (mesh is None or mesh.devices.size == 1))


def _over_rows(fn, *arrays):
    """``fn`` over [B * S, lanes] views of [B, S, lanes] arrays."""
    lead = arrays[0].shape[:-1]
    outs = fn(*(t.reshape(-1, t.shape[-1]) for t in arrays))
    one = not isinstance(outs, tuple)
    outs = tuple(o.reshape(lead + o.shape[-1:])
                 for o in ((outs,) if one else outs))
    return outs[0] if one else outs


@register_op(OperatorType.HC_PRE)
class HyperConnectionPre(Op):
    """stream [B, S, n*C] -> (h [B, S, C], maps [B, S, 128] float32,
    the stream). Leaves (float32): phi_pre, phi_post [n*C, n], phi_res
    [n*C, n*n], b_pre, b_post [n], b_res [n, n], alpha [3]."""

    scopes_itself = SCOPE
    exports = 2
    # the third output is the first input's buffer: it occupies nothing
    # and is not written (search/unity.py, native `aliased_outputs`)
    aliased_outputs = 1
    full_precision_params = ("phi_pre", "phi_post", "phi_res", "b_pre",
                             "b_post", "b_res", "alpha")

    def __init__(self, layer, input_shapes):
        p = layer.properties
        self.streams = int(p["streams"])
        self.sinkhorn_iters = int(p.get("sinkhorn_iters", 20))
        self.eps = float(p.get("eps", 1e-6))
        self.clamp = (float(p.get("clamp_min", -30.0)),
                      float(p.get("clamp_max", 30.0)))
        width = input_shapes[0][-1]
        if (len(input_shapes[0]) != 3 or self.streams < 1
                or width % self.streams
                or map_count(self.streams) + 2 * self.streams >= MAP_LANES):
            raise ValueError(
                f"hc_pre '{layer.name}': a stream [B, S, n*C] of n = "
                f"{self.streams} copies (got {list(input_shapes[0])})")
        self._traced = self._kernel = False
        self._counters = None
        super().__init__(layer, input_shapes)

    @property
    def width(self) -> int:
        return self.input_shapes[0][-1] // self.streams

    def compute_output_shapes(self):
        b, s, wide = self.input_shapes[0]
        return [(b, s, self.width), (b, s, MAP_LANES), (b, s, wide)]

    def init_params(self, rng):
        n, wide = self.streams, self.input_shapes[0][-1]
        alpha, std, diagonal, noise, res_noise = INIT
        ks = jax.random.split(rng, 6)

        def normal(key, shape, scale):
            return scale * jax.random.normal(key, shape, F32)

        return {"phi_pre": normal(ks[0], (wide, n), std),
                "phi_post": normal(ks[1], (wide, n), std),
                "phi_res": normal(ks[2], (wide, n * n), std),
                "b_pre": normal(ks[3], (n,), noise),
                "b_post": normal(ks[4], (n,), noise),
                "b_res": diagonal * jnp.eye(n, dtype=F32)
                + normal(ks[5], (n, n), res_noise),
                "alpha": jnp.full((3,), alpha, F32)}

    def forward(self, params, inputs, ctx: OpContext):
        (x,) = inputs
        n, k = self.streams, map_count(self.streams)
        self._traced = True
        self._kernel = by_kernel(ctx.mesh, x.shape, n)
        if self._kernel:
            from flexflow_tpu.ops.pallas_kernels import hc_read_lanes
            read = hc_read_lanes
        else:
            read = read_plain

        def maps_of(zr, a, b):
            wide = [(0, 0)] * (zr.ndim - 1) + [(0, MAP_LANES - k)]
            logits = a * zr[..., MAP_LANES - 1:] * zr[..., :k] + b
            if self._kernel:
                from flexflow_tpu.ops.pallas_kernels import hc_maps_lanes
                maps = _over_rows(lambda rows: hc_maps_lanes(
                    rows, n, self.sinkhorn_iters, self.eps, self.clamp),
                    jnp.pad(logits, wide))
            else:
                maps = hc_maps(logits, n, self.sinkhorn_iters, self.eps,
                               self.clamp)
                res = jax.lax.stop_gradient(maps[..., 2 * n:]).reshape(
                    maps.shape[:-1] + (n, n))
                maps = jnp.pad(jnp.concatenate(
                    [maps] + [jnp.abs(jnp.sum(res, axis=axis) - 1.0)
                              for axis in (-1, -2)], axis=-1),
                    wide[:-1] + [(0, MAP_LANES - k - 2 * n)])
            # the witness: the largest departure of H_res's row and
            # column sums from one (lanes K.. of the maps, no gradient)
            errs = tuple(jnp.max(jax.lax.stop_gradient(
                maps[..., k + i * n:k + (i + 1) * n])) for i in range(2))
            return maps, errs

        def pre(params, x):
            p = {name: t.astype(F32) for name, t in params.items()}
            phi = jnp.concatenate(
                [p["phi_pre"], p["phi_post"], p["phi_res"]], axis=1)
            a = jnp.concatenate([jnp.broadcast_to(p["alpha"][i], (m,))
                                 for i, m in enumerate((n, n, n * n))])
            b = jnp.concatenate([p["b_pre"], p["b_post"],
                                 p["b_res"].reshape(-1)])
            h, zr, x_out = scoped("hc_read", lambda x, phi, a, b: _over_rows(
                lambda x2: read(x2, phi, a, b, n, self.eps), x))(
                    x, phi, a, b)
            maps, errs = scoped("hc_maps", maps_of)(zr, a, b)
            return h, maps, x_out, errs

        h, maps, x_out, (rows, cols) = scoped(SCOPE, pre)(params, x)
        self._counters = {"hc/res_row_sum_err_max": ("max", rows),
                          "hc/res_col_sum_err_max": ("max", cols)}
        return [h, maps, x_out]

    def traced_gauges(self):
        """`hc/sublayers`: the hyper-connections whose forward has been
        traced; `hc/streams` and `hc/sinkhorn_iters`: their sizes (the
        largest op's); `hc/kernel_fallbacks`: those that ran the
        `jax.numpy` passes and not the kernels (every one on the CPU)."""
        on = int(self._traced)
        return {"hc/sublayers": on, "hc/streams": on * self.streams,
                "hc/sinkhorn_iters": on * self.sinkhorn_iters,
                "hc/kernel_fallbacks": int(self._traced
                                           and not self._kernel)}

    def output_dim_roles(self):
        return [(DimRole.SAMPLE, DimRole.OTHER, DimRole.OTHER)] * 3

    def flops(self):
        b, s, wide = self.input_shapes[0]
        k = map_count(self.streams)
        # the products with phi, the statistic, h; the maps a position
        return b * s * (2 * wide * k + 4 * wide
                        + 8 * self.sinkhorn_iters * self.streams ** 2)

    def interior_bytes(self):
        """What the op keeps beside its outputs: the products and the
        statistic, [B, S, 128] float32 (at the op's own element size)."""
        b, s, _ = self.input_shapes[0]
        return b * s * MAP_LANES * 4

    def params_elems(self):
        n = self.streams
        return (self.input_shapes[0][-1] + 1) * map_count(n) + 3


@register_op(OperatorType.HC_POST)
class HyperConnectionPost(Op):
    """(stream [B, S, n*C], branch output [B, S, C], maps [B, S, 128])
    -> the new stream. No leaves: the maps are HC_PRE's."""

    scopes_itself = SCOPE

    def __init__(self, layer, input_shapes):
        self.streams = int(layer.properties["streams"])
        x, y, maps = (tuple(s) for s in input_shapes)
        if (len(x) != 3 or y != x[:2] + (x[2] // self.streams,)
                or x[2] % self.streams or maps != x[:2] + (MAP_LANES,)):
            raise ValueError(
                f"hc_post '{layer.name}': (stream [B, S, n*C], output "
                f"[B, S, C], maps [B, S, {MAP_LANES}]) with n = "
                f"{self.streams} (got {list(input_shapes)})")
        self._traced = self._kernel = False
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        return [tuple(self.input_shapes[0])]

    def forward(self, params, inputs, ctx: OpContext):
        x, y, maps = inputs
        n = self.streams
        self._traced = True
        self._kernel = by_kernel(ctx.mesh, x.shape, n)
        if self._kernel:
            from flexflow_tpu.ops.pallas_kernels import hc_write_lanes
            write = hc_write_lanes
        else:
            write = write_plain
        return [scoped(SCOPE, scoped("hc_write", lambda x, y, maps: _over_rows(
            lambda *rows: write(*rows, n), x, y.astype(x.dtype),
            maps.astype(F32))))(x, y, maps)]

    def traced_gauges(self):
        return {"hc/kernel_fallbacks": int(self._traced
                                           and not self._kernel)}

    def output_dim_roles(self):
        return [(DimRole.SAMPLE, DimRole.OTHER, DimRole.OTHER)]

    def flops(self):
        b, s, wide = self.input_shapes[0]
        return 2 * b * s * wide * (self.streams + 1)
