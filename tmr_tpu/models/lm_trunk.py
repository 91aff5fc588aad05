"""A language model's trunk as the detector's backbone: a list of typed
decoder layers (mixer ``kda`` | ``mla`` | ``ssm`` | ``gqa``, feed-forward
``dense`` | ``moe``) between SAM's patch embedding and SAM's neck.

Three published families are built from it (``TRUNK_CONFIGS``), each as
published, with no bias on a linear layer, SiLU, pre-norm sub-layers and
causal over the patches in raster order (position = raster index):

- Kimi-Linear's: gated delta-rule linear attention 3 : 1 latent attention
  without rotary (no positional encoding anywhere), the plain residual add,
  RMSNorm eps 1e-5;
- Xing4.0's: latent attention in every layer with a low-rank query and
  YaRN rotary on the "rope" dims (``ops/rope.py``), and in place of the
  residual add ``hc_mult`` streams mixed by manifold-constrained
  hyper-connections (``ops/hyper_conn.py``), RMSNorm eps 1e-6;
- Granite 4.0-H's: Mamba-2 state-space layers (``ops/ssd.py``) 9 : 1
  grouped-query attention without rotary, experts chosen by the largest
  logits and weighed by a softmax over the chosen, beside a shared MLP of a
  width of its own, both sub-layers' outputs scaled by
  ``residual_multiplier`` ahead of the plain add and what stands in the
  embedding's place by ``embedding_multiplier``, RMSNorm eps 1e-5.

The first two's feed-forwards are dense or sigmoid-routed experts with a
shared expert. The token embedding and the output head are on no path of a
detector: the patch embedding stands in the embedding's place and the neck
in the head's. A layer that has experts is told which it holds
(``expert_offset``, ``experts_held``): it routes over all ``num_experts``
and computes its own experts' part (``ops/moe.py``).

The trunk's leaves are bfloat16 (``param_dtype``), as the model is published
and served; the router's and the gates' arithmetic is float32; the rest runs
in ``dtype``. Patch embedding and neck are ``models/vit.py``'s own.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tmr_tpu.diagnostics import mosaic_off_reason
from tmr_tpu.models.vit import apply_neck, neck_modules, patch_embed_conv
from tmr_tpu.obs import metrics
from tmr_tpu.ops import hyper_conn, rope as rope_ops
from tmr_tpu.ops import moe as moe_ops
from tmr_tpu.ops import ssd as ssd_ops
from tmr_tpu.ops.causal_attn import (causal_attention_blocked,
                                      gqa_formulation,
                                      latent_attention_blocked,
                                      latent_attention_kernel,
                                      mla_formulation)
from tmr_tpu.ops.kda import (HI, causal_conv, kda_chunk_kernel, kda_chunked,
                             kda_formulation, l2norm, rms_norm)

#: the collection the expert layers leave their group sizes and their
#: choice of experts in; a program that makes it mutable gets them back
#: with its outputs (inference.py)
STATS = "trunk_stats"

#: what each mechanism traces with (counters ``trunk.<kind>.<formulation>``,
#: copied onto the ``compile`` span). All six choose theirs by device, type
#: and sizes: the recurrence (``ops/kda.py:kda_formulation``), latent
#: attention (``ops/causal_attn.py:mla_formulation``, with ``_rope`` after
#: the name where the trunk has rotary), the state-space recurrence
#: (``ops/ssd.py:ssd_formulation``), grouped-query attention
#: (``ops/causal_attn.py:gqa_formulation``), the experts' grouped products
#: (``ops/moe.py:grouped_formulation``) and the hyper-connections
#: (``ops/hyper_conn.py:hc_formulation``). ``KDA_FORMULATION`` and
#: ``MLA_FORMULATION`` are the first two's fallbacks' names, kept as
#: constants for the benchmark's driver alone
#: (``benchmarks/drivers/offline_predict_lm_trunk.py:_say_gates`` prints
#: them, stale on the chip): they go when a ``benchmark`` PR drops that
#: read (ROADMAP.md Design 3a)
KDA_FORMULATION = "chunked_xla"
MLA_FORMULATION = "blocked_xla"


def _weight(module, name, shape, init=None):
    init = init or nn.initializers.lecun_normal()
    return module.param(name, init, shape, module.param_dtype)


def _linear(features, dtype, param_dtype, name, precision=None):
    """``x @ kernel``, no bias; the leaf in ``param_dtype``."""
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=param_dtype, precision=precision, name=name)


class RMSNorm(nn.Module):
    eps: float = 1e-5
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, weight_only: bool = False):
        """``weight_only``: the weight and eps for a caller whose kernel
        normalises ``x``'s like itself."""
        weight = _weight(self, "weight", (x.shape[-1],), nn.initializers.ones)
        if weight_only:
            return weight, self.eps
        return rms_norm(x, weight, self.eps).astype(x.dtype)


class GatedMLP(nn.Module):
    """``down(silu(gate x) * up x)``."""

    width: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        lin = lambda n, name: _linear(n, self.dtype, self.param_dtype, name)
        h = jax.nn.silu(lin(self.width, "gate")(x)) * lin(self.width, "up")(x)
        return lin(x.shape[-1], "down")(h)


class KDAMixer(nn.Module):
    """Gated delta-rule linear attention (``ops/kda.py``)."""

    num_heads: int
    head_dim: int
    conv_size: int = 4
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        h, d = self.num_heads, self.head_dim
        rank = d  # the low-rank decay and output gates go through d
        f32 = jnp.float32
        lin = lambda n, name: _linear(n, self.dtype, self.param_dtype, name)
        lin32 = lambda n, name: _linear(n, f32, self.param_dtype, name, HI)

        def conv_branch(name):
            y = lin(h * d, f"{name}_proj")(x)
            kernel = _weight(self, f"{name}_conv", (self.conv_size, h * d))
            return jax.nn.silu(causal_conv(y, kernel)).reshape(b, s, h, d)

        q, k, v = conv_branch("q"), conv_branch("k"), conv_branch("v")
        # the decay and beta: float32 arithmetic
        a_log = _weight(self, "A_log", (h,),
                        nn.initializers.constant(1.386))
        dt_bias = _weight(self, "dt_bias", (h * d,),
                          nn.initializers.constant(-4.0))
        g = lin32(h * d, "f_b")(lin32(rank, "f_a")(x))
        g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
            g + dt_bias.astype(f32)).reshape(b, s, h, d)
        beta = jax.nn.sigmoid(lin32(h, "b_proj")(x))
        formulation = kda_formulation(s, d, d, self.dtype, h)
        metrics.counter(f"trunk.kda.{formulation}").inc()
        o_norm = RMSNorm(self.norm_eps, self.param_dtype, name="o_norm")
        if formulation == "chunk_kernel":
            # the norms on either side of the recurrence ride in the kernel
            with jax.named_scope("scan"):
                o = kda_chunk_kernel(q, k, v, g, beta, self.dtype, d ** -0.5,
                                     o_norm(v, weight_only=True))
        else:
            q, k = l2norm(q) * d ** -0.5, l2norm(k)
            with jax.named_scope("scan"):
                o = kda_chunked(q, k, v, g, beta, dtype=self.dtype)
            o = o_norm(o)
        gate = jax.nn.sigmoid(
            lin(h * d, "g_b")(lin(rank, "g_a")(x)).astype(f32))
        o = o.reshape(b, s, h * d) * gate
        return lin(x.shape[-1], "o_proj")(o.astype(self.dtype))


class MLAMixer(nn.Module):
    """Latent attention: the query-key width (nope + "rope" dims) differs
    from the value width; one ``k_pe`` is shared by all heads. ``q_rank``:
    the query goes through a low-rank pair (``q_a`` -> RMSNorm -> ``q_b``),
    else one ``q_proj``. ``rope`` (the config's ``rope_scaling`` group and
    ``theta``): the "rope" dims of the query and ``k_pe`` are turned by
    their token's position with YaRN's frequencies and the softmax scale
    takes YaRN's ``mscale^2``; ``None``: unrotated (NoPE). A prefill has no
    use for the latent cache. Scores, softmax and values are
    ``ops/causal_attn.py``'s, on ``q``, ``kv`` and ``k_pe`` as the
    projections wrote them: one Pallas kernel or the row-blocked XLA form,
    as ``mla_formulation`` answers from the device, the type and the
    sizes."""

    num_heads: int
    qk_nope_dim: int
    qk_pe_dim: int
    v_dim: int
    kv_rank: int
    q_rank: Optional[int] = None
    rope: Any = None
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        s = x.shape[1]
        h, dn, dp, dv = (self.num_heads, self.qk_nope_dim, self.qk_pe_dim,
                         self.v_dim)
        lin = lambda n, name: _linear(n, self.dtype, self.param_dtype, name)
        norm = lambda name: RMSNorm(self.norm_eps, self.param_dtype, name=name)
        if self.q_rank:
            q = lin(h * (dn + dp), "q_b")(
                norm("q_a_norm")(lin(self.q_rank, "q_a")(x)))
        else:
            q = lin(h * (dn + dp), "q_proj")(x)
        kv = lin(self.kv_rank + dp, "kv_a")(x)
        c, k_pe = kv[..., :self.kv_rank], kv[..., self.kv_rank:]
        kv = lin(h * (dn + dv), "kv_b")(norm("kv_a_norm")(c))
        scale = (dn + dp) ** -0.5
        rot = None
        if self.rope:
            inv_freq, gain, temper = rope_ops.yarn_rotation(dp, self.rope)
            with jax.named_scope("rope"):
                k_pe = rope_ops.rotate(k_pe, jnp.arange(s), inv_freq, gain)
            rot = (tuple(inv_freq.tolist()), gain)
            scale *= temper
        formulation = mla_formulation(s, h, dn, dp, dv, self.dtype,
                                      rot is not None)
        metrics.counter(
            f"trunk.mla.{formulation}{'_rope' if rot else ''}").inc()
        attend = (latent_attention_kernel if formulation == "causal_kernel"
                  else latent_attention_blocked)
        # q, kv, k_pe as the projections wrote them: the query's rotation,
        # the scores, the softmax and the values are all under softmax/
        with jax.named_scope("softmax"):
            o = attend(q, kv, k_pe, h, scale, rot)
        return lin(x.shape[-1], "o_proj")(o)


class GatedRMSNorm(nn.Module):
    """``rmsnorm(y * silu(z)) * weight``: the gate first, then the norm over
    the whole last axis (one group), in float32."""

    eps: float = 1e-5
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, y, z):
        weight = _weight(self, "weight", (y.shape[-1],), nn.initializers.ones)
        f32 = jnp.float32
        return rms_norm(y.astype(f32) * jax.nn.silu(z.astype(f32)), weight,
                        self.eps)


class SSMMixer(nn.Module):
    """A Mamba-2 state-space mixer (``ops/ssd.py``): one ``in_proj`` to
    ``[z | u B C | dt]``, a depthwise causal convolution with a bias over
    ``[u | B | C]`` together (under ``conv/``), the recurrence of ``heads``
    heads of ``head_dim`` on a state of ``state`` a head with ``B`` and
    ``C`` shared by the heads of each of ``groups`` groups (under ``scan/``),
    the gated norm over the whole inner width, ``out_proj``. ``Delta``, the
    decay and the state are float32."""

    num_heads: int
    head_dim: int
    state: int
    groups: int = 1
    conv_size: int = 4
    chunk: int = 256
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        h, p, n, g = self.num_heads, self.head_dim, self.state, self.groups
        inner, f32 = h * p, jnp.float32
        conv_dim = inner + 2 * g * n
        lin = lambda width, name: _linear(width, self.dtype,
                                          self.param_dtype, name)
        zxbcdt = lin(inner + conv_dim + h, "in_proj")(x)
        z, xbc, dt = (zxbcdt[..., :inner],
                      zxbcdt[..., inner:inner + conv_dim],
                      zxbcdt[..., inner + conv_dim:])
        kernel = _weight(self, "conv_kernel", (self.conv_size, conv_dim))
        bias = _weight(self, "conv_bias", (conv_dim,), nn.initializers.zeros)
        with jax.named_scope("conv"):
            xbc = jax.nn.silu(causal_conv(xbc, kernel)
                              + bias.astype(xbc.dtype))
        u = xbc[..., :inner].reshape(b, s, h, p)
        b_in = xbc[..., inner:inner + g * n].reshape(b, s, g, n)
        c_in = xbc[..., inner + g * n:].reshape(b, s, g, n)
        # Delta about 0.01 and A 4 a head until weights are loaded (the
        # family draws Delta log-uniform in [0.001, 0.1], A uniform in
        # [1, 16]: the benchmark's driver does)
        dt_bias = _weight(self, "dt_bias", (h,),
                          nn.initializers.constant(-4.6))
        a_log = _weight(self, "A_log", (h,), nn.initializers.constant(1.386))
        skip = _weight(self, "D", (h,), nn.initializers.ones)
        delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
        formulation = ssd_ops.ssd_formulation(s, h, p, n, self.dtype)
        metrics.counter(f"trunk.ssm.{formulation}").inc()
        with jax.named_scope("scan"):
            y = ssd_ops.ssd_chunked(u, delta, jnp.exp(a_log.astype(f32)),
                                    b_in, c_in, skip, self.chunk, self.dtype)
        y = GatedRMSNorm(self.norm_eps, self.param_dtype, name="norm")(
            y.reshape(b, s, inner), z)
        return lin(x.shape[-1], "out_proj")(y.astype(self.dtype))


class GQAMixer(nn.Module):
    """Grouped-query causal attention without rotary: ``num_heads`` query
    heads on ``kv_heads`` key-value heads of ``head_dim``, the scores times
    ``scale`` (the family's ``attention_multiplier``, not ``head_dim^-1/2``),
    softmax in float32; scores, softmax and values under ``softmax/``
    (``ops/causal_attn.py:causal_attention_blocked``: the key-value heads are
    not written out once a query head)."""

    num_heads: int
    kv_heads: int
    head_dim: int
    scale: float
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        h, hkv, d = self.num_heads, self.kv_heads, self.head_dim
        lin = lambda width, name: _linear(width, self.dtype,
                                          self.param_dtype, name)
        q = lin(h * d, "q_proj")(x).reshape(b, s, h, d)
        k = lin(hkv * d, "k_proj")(x).reshape(b, s, hkv, d)
        v = lin(hkv * d, "v_proj")(x).reshape(b, s, hkv, d)
        formulation = gqa_formulation(s, h, hkv, d, self.dtype)
        metrics.counter(f"trunk.gqa.{formulation}").inc()
        with jax.named_scope("softmax"):
            o = causal_attention_blocked(q, k, v, self.scale)
        return lin(x.shape[-1], "o_proj")(o.reshape(b, s, h * d))


class Experts(nn.Module):
    """The held experts' stacked weights and their grouped products."""

    held: int
    width: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, xs, group_sizes):
        d = xs.shape[-1]
        formulation = moe_ops.grouped_formulation(
            xs.shape[0], d, self.width, self.dtype)
        metrics.counter(f"trunk.moe.{formulation}").inc()
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        gate = _weight(self, "gate", (self.held, d, self.width), init)
        up = _weight(self, "up", (self.held, d, self.width), init)
        down = _weight(self, "down", (self.held, self.width, d), init)
        return moe_ops.grouped_ffn(xs, group_sizes, gate, up, down,
                                   self.dtype, formulation)


class Router(nn.Module):
    """``kind`` ``sigmoid_bias``: sigmoid scores, chosen with a selection
    bias, renormalised and scaled (``ops/moe.py:route``); ``softmax_topk``:
    the largest logits, weighed by a softmax over the chosen, with neither
    bias nor scale (``route_softmax_topk``)."""

    num_experts: int
    top_k: int
    scale: float
    param_dtype: Any = jnp.bfloat16
    kind: str = "sigmoid_bias"

    @nn.compact
    def __call__(self, x):
        kernel = _weight(self, "kernel", (x.shape[-1], self.num_experts))
        if self.kind == "softmax_topk":
            return moe_ops.route_softmax_topk(x, kernel, self.top_k)
        if self.kind != "sigmoid_bias":
            raise KeyError(f"unknown router kind {self.kind!r}")
        bias = _weight(self, "bias", (self.num_experts,),
                       nn.initializers.zeros)
        return moe_ops.route(x, kernel, bias, self.top_k, self.scale)


class MoEFFN(nn.Module):
    """``shared(x) + sum over the chosen experts held here``; the shared
    MLP is ``shared_width`` wide, or as wide as an expert."""

    num_experts: int
    experts_held: int
    expert_offset: int
    top_k: int
    scale: float
    width: int
    router: str = "sigmoid_bias"
    shared_width: Optional[int] = None
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        off = mosaic_off_reason()
        if off is not None:
            # the one refusal of a mesh, where the exchange would stand:
            # every program XLA partitions over more than one device is
            # traced through parallel/compat.partitioned, which sets this
            raise ValueError(
                "an expert layer inside a program partitioned over a mesh: "
                "the exchange of tokens between the chips that share a "
                f"layer is not written (ROADMAP Reach 11); {off}. Run the "
                "backbone on one device, or as replicas of the one-device "
                "program")
        b, s, d = x.shape
        tokens = x.reshape(b * s, d)
        idx, weights = Router(self.num_experts, self.top_k, self.scale,
                              self.param_dtype, self.router,
                              name="router")(tokens)
        pairs = moe_ops.pairs_formulation(b * s * self.top_k, self.top_k, d,
                                          self.dtype)
        metrics.counter(f"trunk.pairs.{pairs}").inc()
        with jax.named_scope("dispatch"):
            xs, group_sizes, here, slot = moe_ops.dispatch(
                tokens, idx, self.experts_held, self.expert_offset)
        metrics.counter("trunk.experts_held").inc(self.experts_held)
        self.sow(STATS, "tokens", jnp.int32(b * s))
        self.sow(STATS, "group_sizes", group_sizes)
        self.sow(STATS, "chosen", idx)
        ys = Experts(self.experts_held, self.width, self.dtype,
                     self.param_dtype, name="experts")(xs, group_sizes)
        with jax.named_scope("dispatch"):
            routed = moe_ops.combine(ys, weights, here, slot, pairs)
        shared = GatedMLP(self.shared_width or self.width, self.dtype,
                          self.param_dtype, name="shared")(x)
        return shared + routed.reshape(b, s, d).astype(shared.dtype)


class HyperConn(nn.Module):
    """One sub-layer's hyper-connection leaves (``ops/hyper_conn.py``):
    ``phi`` (n C, 2 n + n^2) with columns [pre | post | res], the three
    ``alpha``, and the biases. Initialised so that ``H_res`` starts near the
    identity and the token-dependent part small, as published."""

    n: int
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, width: int):
        n = self.n
        eye = lambda key, shape, dtype: 4.0 * jnp.eye(n, dtype=dtype)
        return dict(
            phi=_weight(self, "phi", (n * width, 2 * n + n * n)),
            alpha=_weight(self, "alpha", (3,),
                          nn.initializers.constant(0.01)),
            b_pre=_weight(self, "b_pre", (n,), nn.initializers.zeros),
            b_post=_weight(self, "b_post", (n,), nn.initializers.zeros),
            b_res=_weight(self, "b_res", (n, n), eye))


class TrunkLayer(nn.Module):
    """A mixer and a feed-forward, each on ``norm(x)``. With ``hc_mult`` 0
    the residual is the plain add, ``x + f(norm(x))`` on (B, S, C), or with
    a ``residual_multiplier`` ``r``, ``x + r f(norm(x))``; else
    ``x`` is ``hc_mult`` streams (n, B, S, C) and each sub-layer reads their
    ``H_pre`` mix and writes back through ``H_res`` and ``H_post``
    (``ops/hyper_conn.py``; one stream is *not* the plain add). ``sizes``
    is the trunk's own (``TRUNK_CONFIGS``)."""

    mixer: str  # "kda" | "mla" | "ssm" | "gqa"
    ffn: str  # "dense" | "moe"
    sizes: Any
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        z = self.sizes
        kw = dict(dtype=self.dtype, param_dtype=self.param_dtype)
        if self.mixer == "kda":
            attn = KDAMixer(z["num_heads"], z["kda_head_dim"],
                            z["conv_size"], z["norm_eps"], name="attn", **kw)
        elif self.mixer == "mla":
            attn = MLAMixer(z["num_heads"], z["qk_nope_dim"],
                            z["qk_pe_dim"], z["v_dim"], z["kv_rank"],
                            z["q_rank"], z["rope"], z["norm_eps"],
                            name="attn", **kw)
        elif self.mixer == "ssm":
            attn = SSMMixer(z["ssm_heads"], z["ssm_head_dim"],
                            z["ssm_state"], z["ssm_groups"], z["conv_size"],
                            z["ssm_chunk"], z["norm_eps"], name="attn", **kw)
        elif self.mixer == "gqa":
            attn = GQAMixer(z["num_heads"], z["kv_heads"], z["head_dim"],
                            z["attn_scale"], name="attn", **kw)
        else:
            raise KeyError(f"unknown mixer kind {self.mixer!r}")
        if self.ffn == "dense":
            mlp = GatedMLP(z["dense_width"], name="ffn", **kw)
        elif self.ffn == "moe":
            mlp = MoEFFN(z["num_experts"], z["experts_held"],
                         z["expert_offset"], z["top_k"], z["routed_scale"],
                         z["expert_width"], z["router"], z["shared_width"],
                         name="ffn", **kw)
        else:
            raise KeyError(f"unknown feed-forward kind {self.ffn!r}")
        norm = lambda name: RMSNorm(z["norm_eps"], self.param_dtype,
                                    name=name)
        if not z["hc_mult"]:
            r = z.get("residual_multiplier")  # a size newer than some callers
            scaled = (lambda y: y) if r is None else (lambda y: r * y)
            x = x + scaled(attn(norm("norm1")(x)))
            return x + scaled(mlp(norm("norm2")(x)))

        def hyper_connected(name, x, f, norm):
            # the scopes <name>/coeff/ and <name>/mix/ hold the
            # hyper-connection's own passes; f and norm keep their own
            p = HyperConn(z["hc_mult"], self.param_dtype, name=name)(
                x.shape[-1])
            formulation = hyper_conn.hc_formulation(
                z["hc_mult"], x.shape[-1], self.dtype)
            metrics.counter(f"trunk.hc.{formulation}").inc()
            with jax.named_scope(name):
                with jax.named_scope("coeff"):
                    h_pre, h_post, h_res = hyper_conn.coefficients(
                        x, p["phi"], p["alpha"], p["b_pre"], p["b_post"],
                        p["b_res"], z["hc_sinkhorn_iters"], z["hc_eps"],
                        z["hc_clamp"], z["norm_eps"])
                with jax.named_scope("mix"):
                    h = hyper_conn.pre_mix(x, h_pre, self.dtype)
            y = f(norm(h))
            with jax.named_scope(name), jax.named_scope("mix"):
                return hyper_conn.post_mix(x, y, h_post, h_res)

        x = hyper_connected("hc_attn", x, attn, norm("norm1"))
        return hyper_connected("hc_ffn", x, mlp, norm("norm2"))


class LMTrunkBackbone(nn.Module):
    """(B, S, S, 3) NHWC -> (B, S/16, S/16, out_chans): patch embedding,
    the trunk over the patches in raster order, final norm, neck. With
    ``hc_mult`` streams the patch embedding is replicated into them ahead
    of the first layer and they are summed ahead of the final norm. A trunk
    states the sizes of the kinds of layer it has and no others."""

    hidden: int
    layers: Sequence[Tuple[str, str]]  # (mixer kind, ffn kind) a layer
    num_heads: int
    expert_width: int
    num_experts: int
    experts_held: int
    top_k: int
    routed_scale: float = 1.0
    qk_nope_dim: Optional[int] = None  # "mla" layers
    qk_pe_dim: Optional[int] = None
    v_dim: Optional[int] = None
    kv_rank: Optional[int] = None
    dense_width: Optional[int] = None  # "dense" feed-forwards
    kda_head_dim: Optional[int] = None  # "kda" layers
    conv_size: Optional[int] = None  # "kda" and "ssm" layers
    ssm_heads: Optional[int] = None  # "ssm" layers
    ssm_head_dim: Optional[int] = None
    ssm_state: Optional[int] = None
    ssm_groups: int = 1
    ssm_chunk: int = 256
    kv_heads: Optional[int] = None  # "gqa" layers
    head_dim: Optional[int] = None
    attn_scale: Optional[float] = None
    router: str = "sigmoid_bias"  # or "softmax_topk"
    shared_width: Optional[int] = None  # None: an expert's own width
    residual_multiplier: Optional[float] = None  # None: x + f(norm(x))
    embedding_multiplier: Optional[float] = None
    q_rank: Optional[int] = None  # None: one q_proj
    rope: Any = None  # None: NoPE; else YaRN's group and "theta"
    hc_mult: int = 0  # 0: the plain residual add
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    norm_eps: float = 1e-5
    expert_offset: int = 0
    patch_size: int = 16
    out_chans: int = 256
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.bfloat16

    def setup(self):
        self._patch = patch_embed_conv(self.hidden, self.patch_size,
                                       self.dtype)
        sizes = {f: getattr(self, f) for f in (
            "num_heads", "kda_head_dim", "conv_size", "qk_nope_dim",
            "qk_pe_dim", "v_dim", "kv_rank", "q_rank", "rope", "dense_width",
            "expert_width", "num_experts", "experts_held", "expert_offset",
            "top_k", "routed_scale", "hc_mult", "hc_sinkhorn_iters",
            "hc_eps", "hc_clamp", "norm_eps", "ssm_heads", "ssm_head_dim",
            "ssm_state", "ssm_groups", "ssm_chunk", "kv_heads", "head_dim",
            "attn_scale", "router", "shared_width", "residual_multiplier")}
        self._layers = [
            TrunkLayer(mixer, ffn, sizes, self.dtype, self.param_dtype,
                       name=f"layers_{i}")
            for i, (mixer, ffn) in enumerate(self.layers)]
        self._final_norm = RMSNorm(self.norm_eps, self.param_dtype,
                                   name="final_norm")
        self._neck = neck_modules(self.out_chans, self.dtype)

    def embed(self, x: jnp.ndarray) -> jnp.ndarray:
        return self._patch(x)

    def neck(self, x: jnp.ndarray) -> jnp.ndarray:
        return apply_neck(self._neck, x, self.dtype)

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = self.embed(x)
        if self.embedding_multiplier is not None:
            x = x * self.embedding_multiplier
        b, h, w, d = x.shape
        x = x.reshape(b, h * w, d)
        if self.hc_mult:
            x = jnp.broadcast_to(x[None], (self.hc_mult,) + x.shape)
        for layer in self._layers:
            x = layer(x)
        if self.hc_mult:
            x = x.astype(jnp.float32).sum(0).astype(self.dtype)
        return self.neck(self._final_norm(x).reshape(b, h, w, d))


def _pattern(n_layers: int, mla_every: int = 4, first_dense: int = 1):
    """The published pattern: every ``mla_every``-th layer (1-based) is
    latent attention, the others KDA; the first ``first_dense`` layers have
    a dense feed-forward, the others experts."""
    return tuple(
        ("mla" if (i + 1) % mla_every == 0 else "kda",
         "dense" if i < first_dense else "moe") for i in range(n_layers))


#: name -> sizes. ``kimi_linear_a3b_share2``: the published widths of
#: Kimi-Linear-48B-A3B, layers 1 to 5, the 128 of 256 experts that one of
#: the two chips sharing a layer holds. ``xing4_a4b_stage6``: the published
#: widths of Xing4.0-29B-A4B, one pipeline stage's six layers (published 2
#: to 7: the second leading dense layer and five expert layers), every one
#: of a layer's 64 experts held (``ep_size`` 1).
#: ``granite4_h_small_share2``: the published widths of Granite 4.0-H Small
#: (32B-A9B), one period of its pattern (published layers 7 to 16: nine
#: state-space layers and one attention layer, a pipeline stage of ten), the
#: 36 of 72 experts that one of the two chips sharing a layer holds. (The
#: tests and the benchmark's rehearsals add the same patterns at tiny widths
#: under names of their own.)
TRUNK_CONFIGS = {
    "kimi_linear_a3b_share2": dict(
        hidden=2304, layers=_pattern(5), num_heads=32, kda_head_dim=128,
        conv_size=4, qk_nope_dim=128, qk_pe_dim=64, v_dim=128, kv_rank=512,
        dense_width=9216, expert_width=1024, num_experts=256,
        experts_held=128, top_k=8, routed_scale=2.446),
    "xing4_a4b_stage6": dict(
        hidden=3584, layers=(("mla", "dense"),) + (("mla", "moe"),) * 5,
        num_heads=32, qk_nope_dim=128, qk_pe_dim=64, v_dim=128, kv_rank=512,
        q_rank=768, dense_width=9216, expert_width=1024, num_experts=64,
        experts_held=64, top_k=4, routed_scale=2.0, norm_eps=1e-6,
        rope=dict(theta=10000, factor=64, beta_fast=32, beta_slow=1,
                  mscale=1, mscale_all_dim=1,
                  original_max_position_embeddings=4096),
        hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, hc_clamp=(-30.0, 30.0)),
    "granite4_h_small_share2": dict(
        hidden=4096, layers=(("ssm", "moe"),) * 9 + (("gqa", "moe"),),
        ssm_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
        conv_size=4, ssm_chunk=256, num_heads=32, kv_heads=8, head_dim=128,
        attn_scale=0.0078125, expert_width=768, shared_width=1536,
        num_experts=72, experts_held=36, top_k=10, router="softmax_topk",
        residual_multiplier=0.22, embedding_multiplier=12.0),
}


def build_lm_trunk(name: str, dtype=jnp.float32, **overrides):
    return LMTrunkBackbone(dtype=dtype, **{**TRUNK_CONFIGS[name],
                                           **overrides})
