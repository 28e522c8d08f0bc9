"""Mamba-2 mixer (state-space duality, Dao & Gu 2024): the one op in this
library that recurs over the sequence.

No analog in the reference's src/ops. The op is the whole mixer, as
MultiHeadAttention is the whole attention sublayer: input projection,
causal depthwise 1-D convolution, the selective state-space recurrence,
the gated group RMSNorm and the output projection, so that the search
prices and places it as one node and the device trace shows it under one
scope (`ssm_mixer`, inside it `ssd_scan`).

    [z, xBC, dt] = x W_in               widths d_inner, d_inner + 2 G N, H
    xBC = silu(conv1d_causal_depthwise(xBC, k) + b_conv)
    x [H, P], B [G, N], C [G, N] = split(xBC)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)          (a head)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t           (head h reads
    y_t = h_t C_t + D x_t                                   group h // (H/G))
    y = GroupRMSNorm(y * silu(z)) * scale                  (G groups)
    out = y W_out

The recurrence is computed in chunks of `chunk_size` positions: inside a
chunk by the masked decay matrix (matrix products on the MXU), between
chunks by the state that leaves one and enters the next. Decays and
cumulative sums are float32 whatever the compute dtype; the state is
zero at the start of every sequence; the decay exponent is masked BEFORE
the exponential, so the upper triangle contributes exact zeros to value
and gradient alike. Where Pallas is on and `ssd_shape_legal` admits the
shape (`SSMMixer.scans_by_kernel`) that is ONE kernel forward and one
backward (`pallas_kernels.ssd_scan`, PR 62: a chunk's tiles and the
state stay in VMEM, the backward is the chunk function's `jax.vjp`
inside the kernel) under the scope `ssd_scan`; everywhere else it is
`ssd_chunked`, the same in `jax.numpy` (every chunk's own end state as
an array, a `lax.scan` over them, the backward by autodiff).
`ssd_stepwise` is the same recurrence one position at a time, for tests.

Beside it the Mamba-1 mixer (`MambaMixer`, PR 52), whose decay differs by
channel AND state, so that no chunked-product form exists:

    [x ; z] = h W_in                    widths d_inner each, no bias
    x = silu(conv1d_causal_depthwise(x, K) + b_conv)
    [r ; B ; C] = x W_x                 widths R, N, N
    dt = softplus(r W_dt + b_dt);  A = -exp(A_log)         [d_inner, N]
    h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] x_t[c] B_t[n]
    y_t[c] = sum_n h_t[c, n] C_t[n] + D[c] x_t[c]
    out = (y * silu(z)) W_out

The recurrence is ONE Pallas kernel forward and one backward
(`pallas_kernels.selective_scan`: time walked inside the kernel, the
state resident in VMEM) under the scope `selective_scan` inside
`mamba_mixer`; `selective_scan_stepwise` is the same in `jax.numpy`, a
`lax.scan` a position, where Pallas is off and for the tests. With
``export_memory`` the op has a SECOND output, y before its gate, which
other layers read as their memory (`models/decoder.py`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.initializers import DefaultWeightInitializer
from flexflow_tpu.ops.base import (DimRole, Op, OpContext, register_op,
                                   scoped)


def causal_depthwise_conv1d(x, w, b=None, reverse=False):
    """x [B, L, C], w [K, C], b [C] or None -> [B, L, C] float32:
    y_t = sum_j w[j] * x_{t - (K-1) + j} (+ b), zeros before the start.
    ``reverse``: y_t = sum_j w[j] * x_{t + (K-1) - j}, zeros after the
    end, which is the transpose of the first form in x (what its
    backward applies to the cotangent)."""
    k = w.shape[0]
    length = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (0, k - 1) if reverse else (k - 1, 0), (0, 0)))
    y = 0.0 if b is None else b.astype(jnp.float32)
    for j in range(k):
        at = k - 1 - j if reverse else j
        y = y + xp[:, at:at + length].astype(jnp.float32) * w[j].astype(
            jnp.float32)
    return y


def ssd_stepwise(x, dt, a, bm, cm):
    """The recurrence as written, one position a step, float32.
    x [B, L, H, P], dt [B, L, H], a [H], bm / cm [B, L, G, N] -> y [B, L,
    H, P] (without the D skip)."""
    b, _, h, p = x.shape
    g, n = bm.shape[2:]
    rep = h // g

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        b_h = jnp.repeat(b_t, rep, axis=1)        # [B, H, N]
        c_h = jnp.repeat(c_t, rep, axis=1)
        decay = jnp.exp(dt_t * a)[..., None, None]
        state = decay * state + (dt_t[..., None] * x_t)[..., None] \
            * b_h[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_h)

    seq = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0)
                for t in (x, dt, bm, cm))
    _, ys = jax.lax.scan(step, jnp.zeros((b, h, p, n), jnp.float32), seq)
    return jnp.moveaxis(ys, 0, 1)


def ssd_chunked(x, dt, a, bm, cm, chunk, compute_dtype=jnp.float32):
    """The same recurrence in chunks of `chunk` positions. Matrix products
    take `compute_dtype` operands and accumulate in float32; decays,
    cumulative sums and the carried state are float32. A length the chunk
    does not divide is padded with dt = 0 (decay 1, no input), which
    changes nothing before the padding."""
    b, length, h, p = x.shape
    g, n = bm.shape[2:]
    rep = h // g
    pad = (-length) % chunk
    if pad:
        x, dt, bm, cm = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (t.ndim - 2)) for t in (x, dt, bm, cm))
    nc = (length + pad) // chunk
    f32 = jnp.float32
    cd = compute_dtype
    dt = dt.astype(f32)
    xd = (x.astype(f32) * dt[..., None]).reshape(b, nc, chunk, g, rep, p)
    bc = bm.reshape(b, nc, chunk, g, n).astype(cd)
    cc = cm.reshape(b, nc, chunk, g, n).astype(cd)
    # log-decay up to and including each position of its chunk
    cs = jnp.cumsum((dt * a.astype(f32)).reshape(b, nc, chunk, g, rep),
                    axis=2)
    cs = jnp.moveaxis(cs, 2, -1)                       # [b, nc, g, rep, Q]
    # inside a chunk: (C B^T) masked by the decay from s to l
    seg = cs[..., :, None] - cs[..., None, :]           # [.., l, s]
    tril = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(tril, seg, -jnp.inf))
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc,
                    preferred_element_type=f32)
    scores = (cb[:, :, :, None] * decay).astype(cd)     # [b,nc,g,rep,l,s]
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", scores, xd.astype(cd),
                   preferred_element_type=f32)
    # every chunk's own end state: inputs decayed to the chunk's end
    to_end = jnp.exp(cs[..., -1:] - cs)                 # [b,nc,g,rep,Q]
    xd_end = (xd * jnp.moveaxis(to_end, -1, 2)[..., None]).astype(cd)
    own = jnp.einsum("bcsgn,bcsgrp->bcgrpn", bc, xd_end,
                     preferred_element_type=f32)
    # between chunks: state entering chunk c, by the recurrence
    total = jnp.exp(cs[..., -1])                        # [b, nc, g, rep]

    def carry(state, inp):
        own_c, total_c = inp
        return total_c[..., None, None] * state + own_c, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((b, g, rep, p, n), f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(total, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)             # [b,nc,g,rep,p,n]
    y_in = jnp.einsum("bclgn,bcgrpn->bclgrp", cc, entering.astype(cd),
                      preferred_element_type=f32)
    y = y + y_in * jnp.moveaxis(jnp.exp(cs), -1, 2)[..., None]
    return y.reshape(b, nc * chunk, h, p)[:, :length]


def gated_group_rms_norm(y, z, scale, groups, eps):
    """RMSNorm of y * silu(z) over `groups` equal groups of the last dim,
    then the learned scale; float32."""
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = y.shape
    yg = y.reshape(shape[:-1] + (groups, shape[-1] // groups))
    yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    return yg.reshape(shape) * scale.astype(jnp.float32)


def dt_bias_init(rng, shape, dt_min, dt_max, dt_floor):
    """The Mamba initialisation of dt_bias [``shape``]: dt log-uniform in
    [dt_min, dt_max], floored, then the inverse of softplus."""
    u = jax.random.uniform(rng, shape, jnp.float32)
    dt = jnp.exp(u * (math.log(dt_max) - math.log(dt_min))
                 + math.log(dt_min))
    dt = jnp.maximum(dt, dt_floor)
    return dt + jnp.log(-jnp.expm1(-dt))


@register_op(OperatorType.SSM_MIXER)
class SSMMixer(Op):
    """input [B, S, E] -> [B, S, E]. Weights: w_in [E, 2 d_inner + 2 G N +
    H], conv_w [K, d_inner + 2 G N], conv_b, dt_bias [H], a_log [H], d
    [H], norm_scale [d_inner], w_out [d_inner, E]. The per-head rates
    (dt_bias, a_log, d) stay float32 in the compute copy: they set every
    position's decay."""

    scopes_itself = "ssm_mixer"

    full_precision_params = ("dt_bias", "a_log", "d")

    def __init__(self, layer, input_shapes):
        p = layer.properties
        self.num_heads = p["num_heads"]
        self.head_dim = p["head_dim"]
        self.n_groups = p.get("n_groups", 1)
        self.state_size = p["state_size"]
        self.conv_kernel = p.get("conv_kernel", 4)
        self.chunk_size = p.get("chunk_size", 128)
        self.eps = p.get("eps", 1e-5)
        self.dt_range = (p.get("time_step_min", 1e-3),
                         p.get("time_step_max", 1e-1),
                         p.get("time_step_floor", 1e-4))
        if self.num_heads % self.n_groups:
            raise ValueError(
                f"ssm_mixer '{layer.name}': num_heads ({self.num_heads}) "
                f"must be a multiple of n_groups ({self.n_groups})")
        self.d_inner = self.num_heads * self.head_dim
        self.conv_dim = self.d_inner + 2 * self.n_groups * self.state_size
        self.kernel_init = (p.get("kernel_initializer")
                            or DefaultWeightInitializer())
        # whether the last traced forward ran the kernels (`traced_gauges`)
        self._in_kernel = None
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        return [tuple(self.input_shapes[0])]

    def scans_by_kernel(self, mesh, seq=None) -> bool:
        """Whether the scan over ``seq`` positions (the op's own by
        default) runs as the kernel pair `pallas_kernels.ssd_scan`:
        Pallas on, a shape `ssd_shape_legal` admits, and one device (a
        bare kernel call has no partitioning); else `ssd_chunked`."""
        from flexflow_tpu.ops import pallas_kernels as pk
        seq = self.input_shapes[0][1] if seq is None else seq
        return bool(pk.pallas_mode() != "off"
                    and pk.ssd_shape_legal(
                        seq, self.num_heads, self.head_dim, self.n_groups,
                        self.state_size, self.chunk_size)
                    and (mesh is None or mesh.devices.size == 1))

    def init_params(self, rng):
        e = self.input_shapes[0][-1]
        h = self.num_heads
        ks = jax.random.split(rng, 5)
        bound = 1.0 / math.sqrt(self.conv_kernel)
        return {
            "w_in": self.kernel_init(
                ks[0], (e, self.d_inner + self.conv_dim + h)),
            "conv_w": jax.random.uniform(
                ks[1], (self.conv_kernel, self.conv_dim), jnp.float32,
                -bound, bound),
            "conv_b": jnp.zeros((self.conv_dim,)),
            "dt_bias": dt_bias_init(ks[2], (h,), *self.dt_range),
            # A in [1, 16), as the Mamba-2 code draws it
            "a_log": jnp.log(jax.random.uniform(ks[3], (h,), jnp.float32,
                                                1.0, 16.0)),
            "d": jnp.ones((h,)),
            "norm_scale": jnp.ones((self.d_inner,)),
            "w_out": self.kernel_init(ks[4], (self.d_inner, e)),
        }

    def forward(self, params, inputs, ctx: OpContext):
        from flexflow_tpu.ops import pallas_kernels as pk

        (x,) = inputs
        cd = ctx.compute_dtype
        b, s, _ = x.shape
        h, p, g, n = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        self._in_kernel = self.scans_by_kernel(ctx.mesh, s)

        def scan(xbc, dt, a, d):
            """y [B, S, d_inner] float32 with D x, from the convolved
            [x ; B ; C] at the lanes they have."""
            if self._in_kernel:
                return pk.ssd_scan(xbc, dt, a, d, g, n, self.chunk_size)
            xs = xbc[..., :self.d_inner].reshape(b, s, h, p)
            bm = xbc[..., self.d_inner:self.d_inner + g * n]
            cm = xbc[..., self.d_inner + g * n:]
            y = ssd_chunked(xs, dt, a, bm.reshape(b, s, g, n),
                            cm.reshape(b, s, g, n), self.chunk_size, cd)
            y = y + d.astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
            return y.reshape(b, s, self.d_inner)

        def mixer(params, x):
            proj = jnp.einsum("bse,ef->bsf", x.astype(cd),
                              params["w_in"].astype(cd),
                              preferred_element_type=jnp.float32)
            z = proj[..., :self.d_inner].astype(cd)
            xbc = proj[..., self.d_inner:self.d_inner + self.conv_dim]
            dt = jax.nn.softplus(proj[..., self.d_inner + self.conv_dim:]
                                 + params["dt_bias"].astype(jnp.float32))
            xbc = jax.nn.silu(causal_depthwise_conv1d(
                xbc.astype(cd), params["conv_w"], params["conv_b"])
            ).astype(cd)
            a = -jnp.exp(params["a_log"].astype(jnp.float32))
            y = scoped("ssd_scan", scan)(xbc, dt, a, params["d"])
            y = gated_group_rms_norm(y, z, params["norm_scale"], g,
                                     self.eps)
            return jnp.einsum("bsf,fe->bse", y.astype(cd),
                              params["w_out"].astype(cd),
                              preferred_element_type=jnp.float32)

        return [scoped(self.scopes_itself, mixer)(params, x).astype(x.dtype)]

    def traced_gauges(self):
        """`ssm/ssd_kernel_ops`: 1 where the op's scan ran as the Pallas
        kernel pair when it was last traced, 0 where as `ssd_chunked`."""
        return {"ssm/ssd_kernel_ops": int(bool(self._in_kernel))}

    def output_dim_roles(self):
        # the sequence dim recurs: not position-independent, so no SEQ role
        return [(DimRole.SAMPLE, DimRole.OTHER, DimRole.CHANNEL)]

    def scan_flops(self):
        """Forward FLOPs of the chunked scan's four matrix products."""
        b, s, _ = self.input_shapes[0]
        h, p, g, n, q = (self.num_heads, self.head_dim, self.n_groups,
                         self.state_size, self.chunk_size)
        s = -(-s // q) * q
        return 2 * b * s * (q * g * n + q * h * p + 2 * n * h * p)

    def flops(self):
        b, s, e = self.input_shapes[0]
        proj = 2 * b * s * e * (2 * self.d_inner + self.conv_dim
                                + self.num_heads)
        conv = 2 * b * s * self.conv_dim * self.conv_kernel
        return proj + conv + self.scan_flops()

    def interior_bytes(self):
        """Bytes the op keeps for its backward pass besides its output:
        the projection (z, xBC, dt), the convolved xBC and the normalised
        y, at the op's element size; in float32 the state that enters
        every chunk and, at a shape the kernel pair does not take
        (`ssd_chunked`'s arrays), a chunk's decay matrix a head."""
        from flexflow_tpu.ops.pallas_kernels import ssd_shape_legal

        b, s, _ = self.input_shapes[0]
        q = self.chunk_size
        width = 2 * (self.d_inner + self.conv_dim) + self.num_heads
        chunks = -(-s // q)
        tiles = 0 if ssd_shape_legal(
            s, self.num_heads, self.head_dim, self.n_groups,
            self.state_size, q) else q * q
        f32 = 4 * b * chunks * self.num_heads * (
            tiles + self.head_dim * self.state_size)
        return b * s * width * self.dtype.size + f32

    def params_elems(self):
        e = self.input_shapes[0][-1]
        return (e * (self.d_inner + self.conv_dim + self.num_heads)
                + self.conv_dim * (self.conv_kernel + 1)
                + 3 * self.num_heads + self.d_inner + self.d_inner * e)


def selective_scan_stepwise(x, dt, bm, cm, a, d):
    """The Mamba-1 recurrence as written, one position a step, float32:
    x, dt [B, S, C], bm / cm [B, S, N], a [C, N], d [C] -> y [B, S, C]
    (the D skip included)."""
    f32 = jnp.float32
    a, d = a.astype(f32), d.astype(f32)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t[..., None] * a) * state
                 + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return state, jnp.einsum("bcn,bn->bc", state, c_t) + d * x_t

    seq = tuple(jnp.moveaxis(t.astype(f32), 1, 0) for t in (x, dt, bm, cm))
    _, ys = jax.lax.scan(
        step, jnp.zeros((x.shape[0],) + a.shape, f32), seq)
    return jnp.moveaxis(ys, 0, 1)


@register_op(OperatorType.MAMBA_MIXER)
class MambaMixer(Op):
    """input [B, S, E] -> [B, S, E], and with ``export_memory`` a second
    output [B, S, d_inner]: the scan's output y before its gate. Weights:
    w_in [E, 2 d_inner], conv_w [K, d_inner], conv_b, w_x [d_inner, R +
    2 N], w_dt [R, d_inner], dt_bias [d_inner], a_log [d_inner, N], d
    [d_inner], w_out [d_inner, E]. dt_bias, a_log and d stay float32 in
    the compute copy; dt, A, the decays, the state and y are float32."""

    scopes_itself = "mamba_mixer"

    full_precision_params = ("dt_bias", "a_log", "d")

    def __init__(self, layer, input_shapes):
        p = layer.properties
        e = input_shapes[0][-1]
        self.d_inner = p.get("d_inner") or 2 * e
        self.state_size = p.get("state_size", 16)
        self.conv_kernel = p.get("conv_kernel", 4)
        self.dt_rank = p.get("dt_rank") or -(-e // 16)
        self.export_memory = bool(p.get("export_memory", False))
        # a control: the memory taken after the gate
        self.export_gated = bool(p.get("export_gated", False))
        self.dt_range = (p.get("time_step_min", 1e-3),
                         p.get("time_step_max", 1e-1),
                         p.get("time_step_floor", 1e-4))
        self.kernel_init = (p.get("kernel_initializer")
                            or DefaultWeightInitializer())
        # whether the last traced forward ran the kernel (`traced_gauges`)
        self._in_kernel = None
        super().__init__(layer, input_shapes)

    @property
    def exports(self) -> int:
        """Outputs beyond the first that other layers read."""
        return int(self.export_memory)

    def compute_output_shapes(self):
        b, s, e = self.input_shapes[0]
        return [(b, s, e)] + [(b, s, self.d_inner)] * self.exports

    def init_params(self, rng):
        e = self.input_shapes[0][-1]
        di, n, r = self.d_inner, self.state_size, self.dt_rank
        ks = jax.random.split(rng, 6)
        bound = r ** -0.5
        return {
            "w_in": self.kernel_init(ks[0], (e, 2 * di)),
            "conv_w": jax.random.uniform(ks[1], (self.conv_kernel, di),
                                         jnp.float32, -0.5, 0.5),
            "conv_b": jnp.zeros((di,)),
            "w_x": self.kernel_init(ks[2], (di, r + 2 * n)),
            "w_dt": jax.random.uniform(ks[3], (r, di), jnp.float32,
                                       -bound, bound),
            "dt_bias": dt_bias_init(ks[4], (di,), *self.dt_range),
            # A[c, n] = -(n + 1), as the Mamba code sets it
            "a_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (di, n)),
            "d": jnp.ones((di,)),
            "w_out": self.kernel_init(ks[5], (di, e)),
        }

    def forward(self, params, inputs, ctx: OpContext):
        from flexflow_tpu.ops import pallas_kernels as pk

        (x,) = inputs
        cd = ctx.compute_dtype
        f32 = jnp.float32
        di, n, r = self.d_inner, self.state_size, self.dt_rank
        self._in_kernel = pk.pallas_mode() != "off"
        scan = scoped("selective_scan", pk.selective_scan if self._in_kernel
                      else selective_scan_stepwise)

        def dot(a, w):
            return jnp.dot(a.astype(cd), w.astype(cd),
                           preferred_element_type=f32)

        def mixer(params, x):
            proj = dot(x, params["w_in"])
            xs, z = proj[..., :di].astype(cd), proj[..., di:].astype(cd)
            xs = jax.nn.silu(causal_depthwise_conv1d(
                xs, params["conv_w"], params["conv_b"])).astype(cd)
            xdbl = dot(xs, params["w_x"])
            dt = jax.nn.softplus(dot(xdbl[..., :r], params["w_dt"])
                                 + params["dt_bias"].astype(f32))
            y = scan(xs, dt, xdbl[..., r:r + n], xdbl[..., r + n:],
                     -jnp.exp(params["a_log"].astype(f32)), params["d"])
            gated = y * jax.nn.silu(z.astype(f32))
            out = dot(gated, params["w_out"])
            memory = gated if self.export_gated else y
            return [out.astype(x.dtype)] + [
                memory.astype(x.dtype)] * self.exports

        return scoped(self.scopes_itself, mixer)(params, x)

    def traced_gauges(self):
        """`ssm/selective_scan_ops`: Mamba-1 scans the step runs (this
        op's), `ssm/selective_scan_kernel_ops`: of them those that ran
        as the Pallas kernels, as last traced."""
        return {"ssm/selective_scan_ops": 1,
                "ssm/selective_scan_kernel_ops": int(bool(self._in_kernel))}

    def output_dim_roles(self):
        # the sequence dim recurs: not position-independent, so no SEQ role
        return [(DimRole.SAMPLE, DimRole.OTHER, DimRole.CHANNEL)] * (
            1 + self.exports)

    def flops(self):
        b, s, e = self.input_shapes[0]
        di, n, r = self.d_inner, self.state_size, self.dt_rank
        products = 2 * (e * 2 * di + di * (r + 2 * n) + r * di + di * e)
        # a state's update and its read: decay, input, sum (7 a state)
        return b * s * (products + 2 * di * self.conv_kernel + 7 * di * n)

    def interior_bytes(self):
        """Bytes the op keeps for its backward pass besides its outputs:
        the projection (x, z) and the convolved x at the op's element
        size; dt and y in float32; the state entering every chunk of the
        scan kernel."""
        from flexflow_tpu.ops.pallas_kernels import SCAN_CHUNK

        b, s, _ = self.input_shapes[0]
        di = self.d_inner
        return (b * s * 3 * di * self.dtype.size + b * s * 2 * di * 4
                + b * -(-s // SCAN_CHUNK) * self.state_size * di * 4)

    def params_elems(self):
        e = self.input_shapes[0][-1]
        di, n, r = self.d_inner, self.state_size, self.dt_rank
        return (e * 2 * di + di * (self.conv_kernel + 1) + di * (r + 2 * n)
                + r * di + di + di * n + di + di * e)
