"""Gated delta-rule linear attention (Yang et al. 2024, "Gated Delta
Networks"; the `qwen3_next` family's mixer in three layers of four): a
recurrent mixer whose state is CORRECTED, not only decayed and added to.

No analog in the reference's src/ops. The op is the whole mixer, as
SSMMixer, ShortConv and MultiHeadAttention are theirs, so that the search
prices and places it as one node and the device trace shows it under one
scope (`delta_mixer`, inside it `delta_rule`). For the normed input h:

    [q ; k ; v ; z] = h W_qkvz          widths Hk Dk, Hk Dk, Hv Dv, Hv Dv
    [b ; a] = h W_ba                    Hv each
    [q ; k ; v] = silu(conv1d_causal_depthwise([q ; k ; v], K taps))
    beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)   (a value
                                        head and position; float32)
    q = q / sqrt(sum q^2 + 1e-6) * Dk^-1/2;  k = k / sqrt(sum k^2 + 1e-6)
                                        (a head's lanes; key head j
                                        serves value heads j Hv/Hk ..)
    S <- exp(g_t) S;  r_t = S^T k_t                    S [Dk, Dv] a value
    S <- S + k_t (x) (beta_t (v_t - r_t));  o_t = S^T q_t      head, zero
                                                       at the start
    y = (o * rsqrt(mean(o^2) + eps) * w_n) * silu(z)   a value head's Dv
                                        lanes; w_n [Dv] shared by heads
    out = y W_out                       Hv Dv -> E; no bias anywhere

`delta_rule_stepwise` is the recurrence as written, one position a
`lax.scan` step, for the tests. What runs is `delta_rule_chunked`: with
G_t the sum of g over a chunk's rows up to t,

    A = strict_tril(diag(beta) (K K^T) * exp(G_t - G_s))
    T = (I + A)^-1 diag(beta);  W = T (K * exp(G));  U = T V
    V' = U - W S_0;   O = (Q * exp(G)) S_0 + tril(Q K^T * exp(G_t - G_s)) V'
    S_C = exp(G_C) S_0 + (K * exp(G_C - G))^T V'

in one of two bodies, one mathematics. Where Pallas is on, the heads are
128 lanes, the chunk 128 rows and the mesh one device
(`DeltaMixer.walks_by_kernel`): ONE kernel forward and ONE backward
(`pallas_kernels.delta_rule_fused`), everything of a chunk in VMEM and
the state carried from chunk to chunk in scratch, the backward the chunk
function's own `jax.vjp` inside the kernel, a grid step a KEY head whose
value heads walk together (PR 60); the heads' L2 norms and the
gated head norm are rows' sums over a head's 128 lanes and ride in the
same pass (`delta_rule_core`). Else `jax.numpy`: everything
that does not read the state (A, T, W, U, the two decayed copies of q and
k, the masked Q K^T: `_chunk_operands`) batched over all chunks, and the
walk over the chunks with the state carried (`_walk_chunks`: four
products a chunk) a `lax.scan`. Either way the inverse of the unit
lower-triangular I + A is six doublings (I - A)(I + A^2)(I + A^4).. in
float32 at `highest`, with a backward of its own (-T^T dT T^T:
`unit_lower_inverse`). The decay exponent is masked BEFORE the exponential, so the
upper triangle is exact zeros in value and gradient. Float32 whatever
the compute dtype: g, its sums and their exponentials, beta, A and its
inverse, the carried state, the norms; the products' operands are the
compute dtype with float32 accumulation. A length the chunk does not
divide is padded with k = 0, beta = 0, g = 0, which writes nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from flexflow_tpu.ffconst import OperatorType
from flexflow_tpu.initializers import DefaultWeightInitializer
from flexflow_tpu.ops.base import (DimRole, Op, OpContext, register_op,
                                   scoped)
from flexflow_tpu.ops.ssm import causal_depthwise_conv1d, dt_bias_init

HIGHEST = jax.lax.Precision.HIGHEST


def delta_rule_stepwise(q, k, v, g, beta):
    """The recurrence as written, one position a step, float32. q, k
    [B, L, Hk, Dk] (normed and scaled), v [B, L, Hv, Dv], g, beta
    [B, L, Hv] -> o [B, L, Hv, Dv]."""
    b, _, hk, dk = q.shape
    hv, dv = v.shape[2:]

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at
        q_t, k_t = (jnp.repeat(t, hv // hk, axis=1) for t in (q_t, k_t))
        state = jnp.exp(g_t)[..., None, None] * state
        r = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=HIGHEST)
        write = beta_t[..., None] * (v_t - r)
        state = state + k_t[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=HIGHEST)

    seq = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0)
                for t in (q, k, v, g, beta))
    _, out = jax.lax.scan(step, jnp.zeros((b, hv, dk, dv), jnp.float32), seq)
    return jnp.moveaxis(out, 0, 1)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


@jax.custom_vjp
def unit_lower_inverse(a):
    """(I + A)^-1 for A [.., C, C] float32, strictly lower-triangular:
    A^C = 0, so the inverse is (I - A)(I + A^2)(I + A^4).. with
    log2(C) - 1 squarings, every product on the MXU at `highest`."""
    c = a.shape[-1]
    inv = jnp.eye(c, dtype=a.dtype) - a
    power, reach = a, 2     # inv holds the series up to A^(reach - 1)
    while reach < c:
        power = _mm(power, power)
        inv = inv + _mm(inv, power)
        reach *= 2
    return inv


def _unit_lower_inverse_fwd(a):
    t = unit_lower_inverse(a)
    return t, t


def _unit_lower_inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_mm(_mm(tt, dt), tt),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _chunk_operands(q, k, v, g, beta, cd):
    """What a chunk's walk reads, for all chunks at once. q, k [B, N, C,
    Hk, Dk], v [B, N, C, Hv, Dv] in the compute dtype ``cd``, g, beta
    [B, N, C, Hv] float32 -> (Q exp(G), K exp(G_C - G), W, U as [B, N, C,
    Hv, D] and the masked Q K^T as [B, N, C, Hv, C], in ``cd``; exp(G_C)
    [B, N, Hv] float32)."""
    f32 = jnp.float32
    hk, hv = q.shape[3], v.shape[3]
    rep, c = hv // hk, q.shape[2]
    cum = jnp.moveaxis(jnp.cumsum(g, axis=2), 2, -1)        # [B, N, Hv, C]
    seg = cum[..., :, None] - cum[..., None, :]             # [.., t, s]
    rows, cols = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(rows >= cols, seg, -jnp.inf))
    shape = decay.shape[:2] + (hk, rep, c, c)

    def per_value_head(x):      # [B, N, Hk, C, C] against [B, N, Hv, C, C]
        return (x[:, :, :, None] * decay.reshape(shape)).reshape(decay.shape)

    kk = jnp.einsum("bnthd,bnshd->bnhts", k, k, preferred_element_type=f32)
    qk = jnp.einsum("bnthd,bnshd->bnhts", q, k, preferred_element_type=f32)
    bt = jnp.moveaxis(beta, 2, -1)                          # [B, N, Hv, C]
    a = jnp.where(rows > cols, per_value_head(kk) * bt[..., :, None], 0.0)
    t = checkpoint_name(unit_lower_inverse(a), "delta_rule_inverse")
    t = (t * bt[..., None, :]).astype(cd)
    # q and k of a key head laid out for each of its value heads, decayed
    grow = jnp.moveaxis(cum, -1, 2)                         # [B, N, C, Hv]
    end = grow[:, :, -1:]

    def decayed(x, by):
        x = x.astype(f32)[:, :, :, :, None] * by.reshape(
            by.shape[:3] + (hk, rep, 1))
        return x.reshape(x.shape[:3] + (hv, x.shape[-1])).astype(cd)

    k_in = decayed(k, jnp.exp(grow))
    w = jnp.einsum("bnhts,bnshd->bnthd", t, k_in,
                   preferred_element_type=f32).astype(cd)
    u = jnp.einsum("bnhts,bnshd->bnthd", t, v,
                   preferred_element_type=f32).astype(cd)
    p = jnp.moveaxis(per_value_head(qk), 2, 3).astype(cd)   # [B,N,C,Hv,C]
    return (decayed(q, jnp.exp(grow)), decayed(k, jnp.exp(end - grow)), w, u,
            p, jnp.exp(end[:, :, 0]))


def _walk_chunks(qg, kg, w, u, p, a):
    """The chunks one after the other with the state carried, as a
    `lax.scan`. -> o [B, N, C, Hv, Dv] float32."""
    f32 = jnp.float32
    b, _, _, hv, dk = qg.shape
    cd = qg.dtype

    def chunk(state, at):
        qg, kg, w, u, p, a = at
        sb = state.astype(cd)
        vp = u.astype(f32) - jnp.einsum("bthk,bhkv->bthv", w, sb,
                                        preferred_element_type=f32)
        vb = vp.astype(cd)
        o = (jnp.einsum("bthk,bhkv->bthv", qg, sb, preferred_element_type=f32)
             + jnp.einsum("bths,bshv->bthv", p, vb,
                          preferred_element_type=f32))
        state = a[..., None, None] * state + jnp.einsum(
            "bthk,bthv->bhkv", kg, vb, preferred_element_type=f32)
        return state, o

    seq = tuple(jnp.moveaxis(t, 1, 0) for t in (qg, kg, w, u, p, a))
    _, o = jax.lax.scan(chunk, jnp.zeros((b, hv, dk, u.shape[-1]), f32), seq)
    return jnp.moveaxis(o, 0, 1)


def delta_rule_chunked(q, k, v, g, beta, chunk, compute_dtype=jnp.float32):
    """The same recurrence in chunks of ``chunk`` positions, `jax.numpy`
    (module docstring). Shapes as `delta_rule_stepwise`; -> o [B, L, Hv,
    Dv] float32."""
    b, length, _, _ = q.shape
    hv, dv = v.shape[2:]
    pad = (-length) % chunk
    if pad:
        q, k, v, g, beta = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),)
                                    * (t.ndim - 2))
                            for t in (q, k, v, g, beta))
    n = (length + pad) // chunk
    cd = compute_dtype
    q, k, v = (t.astype(cd).reshape((b, n, chunk) + t.shape[2:])
               for t in (q, k, v))
    g, beta = (t.astype(jnp.float32).reshape(b, n, chunk, hv)
               for t in (g, beta))
    # the batched part keeps its inputs and the inverse for the backward
    # pass and forms the rest again: five [B, N, Hv, C, C] float32 arrays
    # a layer otherwise
    operands = jax.checkpoint(
        functools.partial(_chunk_operands, cd=cd),
        policy=jax.checkpoint_policies.save_only_these_names(
            "delta_rule_inverse"))(q, k, v, g, beta)
    o = _walk_chunks(*operands)
    return o.reshape(b, n * chunk, hv, dv)[:, :length]


def delta_rule_core(qkv, z, g, beta, scale, key_heads, chunk, eps,
                    compute_dtype=jnp.float32, kernel=False):
    """From the convolution's output to the output projection's input:
    its SiLU, the heads' L2 norms, the chunked rule, the gated head norm.
    qkv [B, S, 2 Hk Dk + Hv Dv] ([q ; k ; v] as the convolution left them),
    z [B, S, Hv Dv], g, beta [B, S, Hv] float32, scale [Dv] -> y [B, S,
    Hv Dv] in ``compute_dtype``. With ``kernel`` (the caller checks
    `pallas_kernels.delta_rule_shape_legal`) ONE kernel forward and one
    backward, `pallas_kernels.delta_rule_fused`; else `jax.numpy`."""
    b, s, _ = qkv.shape
    hv = g.shape[-1]
    dv = z.shape[-1] // hv
    dk = (qkv.shape[-1] - hv * dv) // (2 * key_heads)
    cd, f32 = compute_dtype, jnp.float32
    if kernel:
        from flexflow_tpu.ops.pallas_kernels import delta_rule_fused
        cum = jnp.cumsum(g.astype(f32).reshape(b, s // chunk, chunk, hv),
                         axis=2).reshape(b, s, hv)

        def rows(t):    # [B, S, Hv] -> [B, Hv, 1, S]
            return jnp.moveaxis(t.astype(f32), 1, 2)[:, :, None]

        return delta_rule_fused(qkv.astype(cd), z.astype(cd), rows(cum),
                                rows(beta), scale.astype(f32)[None],
                                key_heads, eps)
    kd = key_heads * dk
    qkv = jax.nn.silu(qkv.astype(f32))
    q = qkv[..., :kd].reshape(b, s, key_heads, dk)
    k = qkv[..., kd:2 * kd].reshape(b, s, key_heads, dk)
    v = qkv[..., 2 * kd:].reshape(b, s, hv, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        * dk ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    o = delta_rule_chunked(q, k, v, g, beta, chunk, cd)
    y = heads_rms_norm_gated(o, z.reshape(b, s, hv, dv), scale, eps)
    return y.reshape(b, s, hv * dv).astype(cd)


def heads_rms_norm_gated(o, z, scale, eps):
    """(o * rsqrt(mean(o^2) + eps) * scale) * silu(z) over the last axis
    (a value head's lanes), float32: the norm BEFORE the gate."""
    o, z = o.astype(jnp.float32), z.astype(jnp.float32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o * scale.astype(jnp.float32) * jax.nn.silu(z)


@register_op(OperatorType.DELTA_MIXER)
class DeltaMixer(Op):
    """input [B, S, E] -> [B, S, E]. Weights: w_qkvz [E, 2 Hk Dk + 2 Hv
    Dv] (column groups [q ; k ; v ; z]), w_ba [E, 2 Hv] ([b ; a]), conv_w
    [K, 2 Hk Dk + Hv Dv], a_log [Hv], dt_bias [Hv], norm_scale [Dv],
    w_out [Hv Dv, E]. The per-head rates stay float32 in the compute
    copy: they set every position's decay."""

    scopes_itself = "delta_mixer"

    full_precision_params = ("a_log", "dt_bias")

    def __init__(self, layer, input_shapes):
        p = layer.properties
        self.key_heads = p["num_key_heads"]
        self.value_heads = p["num_value_heads"]
        self.key_dim = p["key_head_dim"]
        self.value_dim = p["value_head_dim"]
        self.conv_kernel = p.get("conv_kernel", 4)
        self.chunk_size = p.get("chunk_size", 128)
        self.eps = p.get("eps", 1e-6)
        if self.value_heads % self.key_heads:
            raise ValueError(
                f"delta_mixer '{layer.name}': num_value_heads "
                f"({self.value_heads}) must be a multiple of num_key_heads "
                f"({self.key_heads})")
        self.k_width = self.key_heads * self.key_dim
        self.v_width = self.value_heads * self.value_dim
        self.conv_dim = 2 * self.k_width + self.v_width
        self.kernel_init = (p.get("kernel_initializer")
                            or DefaultWeightInitializer())
        self._traced = self._kernel = False
        self._counters = None
        super().__init__(layer, input_shapes)

    def compute_output_shapes(self):
        return [tuple(self.input_shapes[0])]

    def init_params(self, rng):
        e = self.input_shapes[0][-1]
        ks = jax.random.split(rng, 5)
        bound = 1.0 / self.conv_kernel ** 0.5
        return {
            "w_qkvz": self.kernel_init(ks[0],
                                       (e, self.conv_dim + self.v_width)),
            "w_ba": self.kernel_init(ks[1], (e, 2 * self.value_heads)),
            "conv_w": jax.random.uniform(
                ks[2], (self.conv_kernel, self.conv_dim), jnp.float32,
                -bound, bound),
            # A in (0, 16) as the published code draws it; the step's bias
            # by Mamba's rule (a step log-uniform in [1e-3, 1e-1]), which
            # leaves heads that forget inside a chunk and heads that
            # remember across thousands of positions
            "a_log": jnp.log(jax.random.uniform(
                ks[3], (self.value_heads,), jnp.float32, 1e-3, 16.0)),
            "dt_bias": dt_bias_init(jax.random.fold_in(ks[3], 1),
                                    (self.value_heads,), 1e-3, 1e-1, 1e-4),
            "norm_scale": jnp.ones((self.value_dim,)),
            "w_out": self.kernel_init(ks[4], (self.v_width, e)),
        }

    def walks_by_kernel(self, mesh, seq=None) -> bool:
        """Whether the rule over ``seq`` positions (the op's own by
        default) runs as the kernel pair
        `pallas_kernels.delta_rule_fused`: Pallas on, heads of 128 lanes,
        chunks of 128 rows, whole row blocks, and one device (a bare
        kernel call has no partitioning); else the `lax.scan`."""
        from flexflow_tpu.ops import pallas_kernels as pk
        seq = self.input_shapes[0][1] if seq is None else seq
        return bool(pk.pallas_mode() != "off"
                    and pk.delta_rule_shape_legal(
                        seq, self.key_dim, self.value_dim, self.chunk_size)
                    and (mesh is None or mesh.devices.size == 1))

    def forward(self, params, inputs, ctx: OpContext):
        (x,) = inputs
        cd = ctx.compute_dtype
        f32 = jnp.float32
        b, s, _ = x.shape
        hk, hv = self.key_heads, self.value_heads
        self._traced = True
        self._kernel = self.walks_by_kernel(ctx.mesh, s)

        def mixer(params, x):
            xc = x.astype(cd)
            w = params["w_qkvz"].astype(cd)
            # q, k, v and the gate z as two products: neither is sliced
            # out of the other's result
            pre = jnp.dot(xc, w[:, :self.conv_dim],
                          preferred_element_type=f32).astype(cd)
            z = jnp.dot(xc, w[:, self.conv_dim:],
                        preferred_element_type=f32).astype(cd)
            ba = jnp.dot(xc, params["w_ba"].astype(cd),
                         preferred_element_type=f32)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(params["a_log"].astype(f32)) * jax.nn.softplus(
                ba[..., hv:] + params["dt_bias"].astype(f32))
            # the convolution is linear: its backward keeps `pre` and the
            # taps alone; its SiLU is `delta_rule_core`'s
            qkv = causal_depthwise_conv1d(pre, params["conv_w"]).astype(cd)
            y = scoped("delta_rule", lambda *t: delta_rule_core(
                *t, hk, self.chunk_size, self.eps, cd, self._kernel))(
                    qkv, z, g, beta, params["norm_scale"])
            out = jnp.dot(y, params["w_out"].astype(cd),
                          preferred_element_type=f32)
            decay = jnp.exp(g)
            return out, jnp.min(decay), jnp.mean(decay)

        out, least, mean = scoped(self.scopes_itself, mixer)(params, x)
        chunks = -(-s // self.chunk_size)
        self._counters = {
            # heads x chunks x samples of this forward; an epoch's sum
            "delta/chunks": ("sum", np.float32(b * hv * chunks)),
            # exp(g) over the step: the smallest and the mean, averaged
            # over the ops and steps of an epoch
            "delta/decay_min": ("mean", least),
            "delta/decay_mean": ("mean", mean),
        }
        return [out.astype(x.dtype)]

    def traced_gauges(self):
        """`executor.delta_mixer_ops`: the op's forward has been traced;
        `executor.delta_rule_kernel_ops`: its walk over the chunks ran as
        the kernel pair when it was; `executor.delta_rule_heads_a_step`:
        the value heads one grid step of that pair walks together (0
        where the walk is the `lax.scan`)."""
        from flexflow_tpu.ops.pallas_kernels import delta_heads_a_step
        kernel = int(self._traced and self._kernel)
        return {"executor.delta_mixer_ops": int(self._traced),
                "executor.delta_rule_kernel_ops": kernel,
                "executor.delta_rule_heads_a_step": kernel
                and delta_heads_a_step(self.value_heads // self.key_heads)}

    def output_dim_roles(self):
        # the sequence dim recurs: not position-independent, so no SEQ role
        return [(DimRole.SAMPLE, DimRole.OTHER, DimRole.CHANNEL)]

    def rule_flops(self):
        """Forward FLOPs of the chunked rule's matrix products, padded
        length: a value head and chunk, K K^T and Q K^T (counted a VALUE
        head: the decay differs), the inverse's 2 (log2 C - 1) products
        of C^3, T K, T V, and the walk's W S, Q S, P V', K^T V'."""
        b, s, _ = self.input_shapes[0]
        c, dk, dv = self.chunk_size, self.key_dim, self.value_dim
        doublings = 2 * max(0, (c - 1).bit_length() - 1)
        chunk = 2 * c * (2 * c * dk + doublings * c * c + c * dk + c * dv
                         + 3 * dk * dv + c * dv)
        return b * -(-s // c) * self.value_heads * chunk

    def flops(self):
        b, s, e = self.input_shapes[0]
        proj = 2 * b * s * e * (self.conv_dim + self.v_width
                                + 2 * self.value_heads + self.v_width)
        conv = 2 * b * s * self.conv_dim * self.conv_kernel
        return proj + conv + self.rule_flops()

    def interior_bytes(self):
        """Bytes the op keeps for its backward pass besides its output:
        the projection (q, k, v, z), the convolved q, k, v, the walk's
        five operands and the normed output at the op's element size; the
        inverse a chunk and the state a block of rows in float32."""
        b, s, _ = self.input_shapes[0]
        c = self.chunk_size
        chunks = -(-s // c)
        width = (2 * self.conv_dim + self.v_width + 2 * self.v_width
                 + 3 * self.value_heads * self.key_dim
                 + self.value_heads * c)
        f32 = 4 * b * self.value_heads * (
            chunks * c * c + -(-s // 1024) * self.key_dim * self.value_dim)
        return b * s * width * self.dtype.size + f32

    def params_elems(self):
        e = self.input_shapes[0][-1]
        return (e * (self.conv_dim + self.v_width + 2 * self.value_heads)
                + self.conv_dim * self.conv_kernel + 2 * self.value_heads
                + self.value_dim + self.v_width * e)
