"""Plain reference of the detector on the Xing4.0 trunk: float32,
``highest`` precision, one image at a time, no kernels, no blocks, no
sorting, the hyper-connections one token at a time.

The trunk follows the model's ``config.json`` (``model_type`` ``xing4_0``)
and the papers it names its parts after: latent attention with a low-rank
query and rotary on the "rope" dims (the DeepSeek-V2/V3 family's, YaRN
frequencies and ``mscale``), manifold-constrained hyper-connections in
place of the residual add (arXiv 2512.24880 over arXiv 2409.19606), and the
sigmoid-routed experts with a shared expert that ``reference_lm_trunk``
already writes out (``_moe_ffn``, by import; as are ``_rms_norm``,
``_gated_mlp`` and the stem). Causal over the patches in raster order, a
patch's position its raster index.

- **Streams.** ``X_0`` is the patch embedding replicated ``hc_mult`` times,
  (S, n, C); after the last layer the streams are summed, then the final
  norm, then the neck.
- **A hyper-connected sub-layer**, for one token with streams ``X`` (n, C):
  ``v = flatten(X) / rms(flatten(X))`` with no learned weight;
  ``h = v phi`` split into pre (n), post (n) and res (n x n) and each
  scaled by its ``alpha`` and shifted by its ``b``; ``H_pre = sigmoid``,
  ``H_post = 2 sigmoid``, ``H_res`` = Sinkhorn-Knopp of
  ``exp(clip(h_res))``: ``hc_sinkhorn_iters`` times every row over (its
  sum + ``hc_eps``), then every column likewise. The sub-layer reads
  ``norm(H_pre X)`` and the token's new streams are
  ``H_res X + outer(H_post, y)``.
- **Rotary** by its definition: pair ``i`` of the 64 "rope" dims is
  ``(i, i + 32)`` and is turned, as a complex number, by
  ``exp(1j * position * inv_freq_i)``.

Everything around the trunk is ``reference.py``'s by import. It imports
nothing of the program under test. The weights are the benchmark's own flat
``{"a/b/c": array}`` dict in whatever type the program holds them, read as
float32. ``quant`` is the control, as in ``reference.py``: every matrix
product's operands rounded (the coefficient product ``v phi`` among them;
the n-wide mixes are weighted sums, not products).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.reference import (_correlate, _decode_heads, _dot,
                                  _neck_and_project, _sub, detect,
                                  roi_align_template)
from benchmarks.reference_lm_trunk import (_dense_ffn, _linear, _moe_ffn,
                                           _rms_norm, embed_tokens,
                                           router_scores)

__all__ = ["forward_dense", "detect", "trunk", "embed_tokens",
           "router_scores", "yarn_inv_freq"]

F32 = jnp.float32


def yarn_inv_freq(rope: dict, dim: int, theta: float) -> np.ndarray:
    """(dim / 2,) inverse frequencies, float64: the published ramp between
    the pair that turns ``beta_fast`` times over the original window and the
    one that turns ``beta_slow`` times."""
    factor, window = rope["factor"], rope["original_max_position_embeddings"]
    # the pair index at which a pair turns `beta` times over the window
    at = lambda beta: dim * math.log(window / (2 * math.pi * beta)) / (
        2 * math.log(theta))
    low = max(math.floor(at(rope["beta_fast"])), 0)
    high = min(math.ceil(at(rope["beta_slow"])), dim - 1)
    out = []
    for i in range(dim // 2):
        f = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(f * (1.0 - ramp) + f / factor * ramp)
    return np.asarray(out, np.float64)


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rotate(x, inv_freq, gain: float):
    """x (S, ..., d): token ``t``'s pairs ``(i, i + d/2)`` turned by
    ``t * inv_freq_i``, as complex numbers."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[0], dtype=F32)[:, None] * jnp.asarray(
        inv_freq, F32)[None, :]
    turn = gain * jnp.exp(1j * angle.astype(jnp.complex64))
    turn = turn.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    z = (x[..., :half] + 1j * x[..., half:]) * turn
    return jnp.concatenate([z.real, z.imag], -1).astype(F32)


@functools.partial(jax.jit, static_argnames=("heads", "nope", "v_dim", "eps",
                                             "rope", "quant"))
def _mla(x, p, heads: int, nope: int, v_dim: int, eps: float, rope, quant):
    """x (S, D), already normed -> (S, D). ``rope``: the YaRN group with
    ``theta`` as a sorted tuple of pairs (hashable)."""
    s = x.shape[0]
    rank = p["kv_a_norm/weight"].shape[0]
    c_q = _rms_norm(_linear(x, p["q_a/kernel"], quant), p["q_a_norm/weight"],
                    eps)
    q = _linear(c_q, p["q_b/kernel"], quant).reshape(s, heads, -1)
    kv = _linear(x, p["kv_a/kernel"], quant)
    c, k_pe = kv[:, :rank], kv[:, rank:]
    kv = _linear(_rms_norm(c, p["kv_a_norm/weight"], eps), p["kv_b/kernel"],
                 quant).reshape(s, heads, nope + v_dim)
    r = dict(rope)
    inv_freq = yarn_inv_freq(r, k_pe.shape[-1], r["theta"])
    m_all = _mscale(r["factor"], r["mscale_all_dim"])
    gain = _mscale(r["factor"], r["mscale"]) / m_all
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], inv_freq,
                                                gain)], -1)
    k_pe = _rotate(k_pe, inv_freq, gain)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scale = q.shape[-1] ** -0.5 * m_all * m_all

    def one_head(t):
        q_h, k_h, v_h = t
        k_h = jnp.concatenate([k_h, k_pe], -1)
        scores = _dot("qc,kc->qk", q_h, k_h, quant) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _dot("qk,kc->qc", probs, v_h, quant)

    o = lax.map(one_head, (q.transpose(1, 0, 2),
                           kv[..., :nope].transpose(1, 0, 2),
                           kv[..., nope:].transpose(1, 0, 2)))
    return _linear(o.transpose(1, 0, 2).reshape(s, heads * v_dim),
                   p["o_proj/kernel"], quant)


def _token_coefficients(v_phi, p, n: int, iters: int, hc_eps: float, clamp):
    """One token: ``v phi`` (2n + n^2,) -> H_pre (n,), H_post (n,), H_res
    (n, n)."""
    alpha = p["alpha"].astype(F32)
    h_pre = jax.nn.sigmoid(alpha[0] * v_phi[:n] + p["b_pre"].astype(F32))
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * v_phi[n:2 * n]
                                  + p["b_post"].astype(F32))
    h_res = alpha[2] * v_phi[2 * n:].reshape(n, n) + p["b_res"].astype(F32)
    m = jnp.exp(jnp.clip(h_res, clamp[0], clamp[1]))
    for _ in range(iters):
        m = m / (m.sum(axis=1, keepdims=True) + hc_eps)
        m = m / (m.sum(axis=0, keepdims=True) + hc_eps)
    return h_pre, h_post, m


@functools.partial(jax.jit, static_argnames=("n", "iters", "hc_eps", "clamp",
                                             "eps", "quant"))
def _hc_read(x, p, n: int, iters: int, hc_eps: float, clamp, eps: float,
             quant):
    """x (S, n, C) -> what the sub-layer reads (S, C), and H_post (S, n),
    H_res (S, n, n)."""
    flat = x.reshape(x.shape[0], -1)
    v = flat * lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + eps)
    v_phi = _dot("sc,cd->sd", v, p["phi"].astype(F32), quant)
    h_pre, h_post, h_res = jax.vmap(
        lambda t: _token_coefficients(t, p, n, iters, hc_eps, clamp))(v_phi)
    read = jax.vmap(lambda h, streams: (h[:, None] * streams).sum(0))(h_pre, x)
    return read, h_post, h_res


@jax.jit
def _hc_write(x, y, h_post, h_res):
    """A token's new streams: ``H_res X + outer(H_post, y)``."""
    def one_token(streams, out, post, res):
        mixed = [sum(res[i, j] * streams[j] for j in range(len(post)))
                 for i in range(len(post))]
        return jnp.stack(mixed) + post[:, None] * out[None, :]

    return jax.vmap(one_token)(x, y, h_post, h_res)


def trunk(bb: dict, x, model: dict, quant=None, routing=None, follow=None,
          margin: float = 0.0, rebias=None):
    """The layers, the sum of the streams and the final norm: x (S, D)
    float32 -> (S, D). ``routing``, ``follow``, ``margin`` and ``rebias`` as
    ``reference_lm_trunk.trunk``'s."""
    eps = float(model["rms_norm_eps"])
    n = int(model["hc_mult"])
    hc = dict(n=n, iters=int(model["hc_sinkhorn_iters"]),
              hc_eps=float(model["hc_eps"]),
              clamp=(float(model["mhc_h_res_clamp_min"]),
                     float(model["mhc_h_res_clamp_max"])), eps=eps,
              quant=quant)
    rope = tuple(sorted(dict(model["rope_scaling"],
                             theta=model["rope_theta"]).items()))
    follow = list(follow) if follow is not None else None
    with jax.default_matmul_precision("highest"):
        x = jnp.repeat(x[:, None, :], n, axis=1)
        for i, (mixer, ffn) in enumerate(model["layers"]):
            if mixer != "mla":
                raise KeyError(f"this trunk has no {mixer!r} layer")
            p = _sub(bb, f"layers_{i}/")
            read, post, res = _hc_read(x, _sub(p, "hc_attn/"), **hc)
            y = _mla(_rms_norm(read, p["norm1/weight"], eps),
                     _sub(p, "attn/"), model["num_heads"],
                     model["qk_nope_head_dim"], model["v_head_dim"], eps,
                     rope, quant)
            x = _hc_write(x, y, post, res)
            read, post, res = _hc_read(x, _sub(p, "hc_ffn/"), **hc)
            y = _rms_norm(read, p["norm2/weight"], eps)
            if ffn == "dense":
                y = _dense_ffn(y, _sub(p, "ffn/"), quant)
            else:
                if rebias is not None:
                    path = f"layers_{i}/ffn/router/bias"
                    p["ffn/router/bias"] = rebias(
                        path, router_scores(y, p["ffn/router/kernel"]))
                y, idx, own, counts, need = _moe_ffn(
                    y, _sub(p, "ffn/"), model["num_experts_per_token"],
                    int(model.get("expert_offset", 0)),
                    float(model["routed_scaling_factor"]), quant,
                    follow=jnp.asarray(follow.pop(0)) if follow else None,
                    margin=float(margin))
                if routing is not None:
                    routing.append({"experts": np.asarray(idx),
                                    "own": np.asarray(own),
                                    "counts": np.asarray(counts),
                                    "need": np.asarray(need)})
            x = _hc_write(x, y, post, res)
        return _rms_norm(x.sum(axis=1), bb["final_norm/weight"], eps)


def forward_dense(flat: dict, image, exemplar, model: dict, quant=None,
                  routing=None, follow=None, margin: float = 0.0):
    """One image (S, S, 3) and its exemplar box (4,) -> objectness logits
    (H, W) and ltrb regressions (H, W, 4), float32 numpy; as
    ``reference_lm_trunk.forward_dense`` with this trunk."""
    with jax.default_matmul_precision("highest"):
        bb = _sub(flat, "backbone/")
        x = embed_tokens(bb, image, model, quant)
        _, h, w, d = x.shape
        x = trunk(bb, x.reshape(h * w, d), model, quant, routing, follow,
                  margin)
        fp = _neck_and_project(x.reshape(1, h, w, d), bb,
                               flat["input_proj_0/kernel"],
                               flat["input_proj_0/bias"],
                               bool(model["feature_upsample"]), quant)
        fp_host = np.asarray(fp[0])
        f_tm = _correlate(fp_host, roi_align_template(fp_host, exemplar),
                          quant)
        f_tm = jnp.asarray(f_tm)[None] * flat["matcher/scale"]
        f_cat = jnp.concatenate([fp, f_tm], -1) if model["fusion"] else f_tm
        obj, reg = _decode_heads(f_cat, flat, model["decoder_num_layer"],
                                 quant)
    return np.asarray(obj, np.float32), np.asarray(reg, np.float32)
