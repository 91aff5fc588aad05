"""Plain reference of the detector on the Granite 4.0-H trunk: float32,
``highest`` precision, one image at a time, no kernels, no chunks, no
sorting: the state-space recurrence one token a ``lax.scan`` step, a masked
softmax a head, the held experts one after another.

The trunk follows the model's ``config.json`` (``model_type``
``granitemoehybrid``) and the family's published code. Pre-norm, RMSNorm eps
``rms_norm_eps``, SiLU, no bias on a linear layer, causal over the patches
in raster order, with ``r = residual_multiplier``::

    x <- x + r * mixer(norm1(x))            mixer by layer: "ssm" | "gqa"
    h  = norm2(x)
    x <- x + r * (routed(h) + shared(h))

- **State-space mixer** (Mamba-2): ``[z | xBC | dt] = x W_in``;
  ``xBC = silu(causal_conv4(xBC) + b_conv)``, depthwise over all channels,
  left-padded with zeros; ``[u | B | C] = xBC``;
  ``Delta_t = softplus(dt_t + dt_bias)``; ``a_t = exp(-Delta_t exp(A_log))``;
  ``H_t = a_t H_{t-1} + Delta_t u_t B_t^T`` a head (64 x 128), ``H_0 = 0``;
  ``y_t = H_t C_t + D u_t``; ``y = rmsnorm(y * silu(z)) * w`` (the gate
  first, the norm over the whole inner width); ``out = y W_out``.
- **Attention mixer**: ``q`` 32 heads, ``k``, ``v`` 8 heads of 128, query
  head ``j`` reads key-value head ``j // 4``; no rotary; scores times
  ``attention_multiplier``; a masked softmax a head.
- **Experts**: ``logits = h W_r`` (float32); the ``num_experts_per_token``
  largest logits; ``gates = softmax`` over those alone; expert ``e`` is
  ``W_down,e (silu(W_gate,e h) * W_up,e h)``; ``shared`` is the same form at
  its own width on every token. What the experts that are not held would
  add is left out, as in the program (``model["experts_held"]``,
  ``model["expert_offset"]``).
- **Around the layers**: ``embedding_multiplier`` multiplies what stands in
  the token embedding's place (the patch embedding's output); the final
  norm; the neck.

Everything around the trunk is ``reference.py``'s by import, and the plain
pieces two trunks share (``_rms_norm``, ``_linear``, ``_conv4``,
``_gated_mlp``, the stem) are ``reference_lm_trunk``'s. It imports nothing
of the program under test. The weights are the benchmark's own flat
``{"a/b/c": array}`` dict in whatever type the program holds them, read as
float32. ``quant`` is the control, as in ``reference.py``: every matrix
product's and the convolution's operands rounded (the state's read-out
``H_t C_t`` among them; the state's update is a rank-one sum, not a
product).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks import reference_lm_trunk
from benchmarks.reference import (_correlate, _decode_heads, _dot,
                                  _neck_and_project, _sub, detect,
                                  roi_align_template)
from benchmarks.reference_lm_trunk import (_conv4, _gated_mlp, _linear,
                                           _rms_norm)

__all__ = ["forward_dense", "detect", "trunk", "embed_tokens",
           "router_logits"]

F32 = jnp.float32


@functools.partial(jax.jit, static_argnames=("heads", "state", "groups",
                                             "eps", "quant"))
def _ssm(x, p, heads: int, state: int, groups: int, eps: float, quant):
    """x (S, D), already normed -> (S, D)."""
    s = x.shape[0]
    inner = p["out_proj/kernel"].shape[0]
    conv_dim = inner + 2 * groups * state
    zxbcdt = _linear(x, p["in_proj/kernel"], quant)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv_dim],
                  zxbcdt[:, inner + conv_dim:])
    xbc = jax.nn.silu(_conv4(xbc, p["conv_kernel"], quant)
                      + p["conv_bias"].astype(F32))
    u = xbc[:, :inner].reshape(s, heads, -1)
    rep = heads // groups
    b = jnp.repeat(xbc[:, inner:inner + groups * state].reshape(
        s, groups, state), rep, axis=1)
    c = jnp.repeat(xbc[:, inner + groups * state:].reshape(
        s, groups, state), rep, axis=1)
    delta = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
    decay = jnp.exp(-delta * jnp.exp(p["A_log"].astype(F32)))

    def step(h, t):
        u_t, d_t, a_t, b_t, c_t = t
        h = (a_t[:, None, None] * h
             + (d_t[:, None] * u_t)[:, :, None] * b_t[:, None, :])
        return h, _dot("hpn,hn->hp", h, c_t, quant)

    init = jnp.zeros((heads, u.shape[-1], state), F32)
    _, y = lax.scan(step, init, (u, delta, decay, b, c))
    y = y + p["D"].astype(F32)[:, None] * u
    y = _rms_norm(y.reshape(s, inner) * jax.nn.silu(z), p["norm/weight"],
                  eps)
    return _linear(y, p["out_proj/kernel"], quant)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "scale",
                                             "quant"))
def _gqa(x, p, heads: int, kv_heads: int, scale: float, quant):
    """x (S, D), already normed -> (S, D): a masked softmax a head, the
    key-value heads repeated."""
    s = x.shape[0]
    q = _linear(x, p["q_proj/kernel"], quant).reshape(s, heads, -1)
    rep = heads // kv_heads
    k = jnp.repeat(_linear(x, p["k_proj/kernel"], quant).reshape(
        s, kv_heads, -1), rep, axis=1)
    v = jnp.repeat(_linear(x, p["v_proj/kernel"], quant).reshape(
        s, kv_heads, -1), rep, axis=1)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_head(t):
        q_h, k_h, v_h = t
        scores = _dot("qc,kc->qk", q_h, k_h, quant) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return _dot("qk,kc->qc", probs, v_h, quant)

    o = lax.map(one_head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return _linear(o.transpose(1, 0, 2).reshape(s, -1), p["o_proj/kernel"],
                   quant)


@jax.jit
def router_logits(x, kernel):
    """``x W_r`` over all the experts of the model, float32."""
    return _dot("sc,ce->se", x, kernel.astype(F32), None)


@functools.partial(jax.jit, static_argnames=("top_k", "offset", "quant"))
def _moe_ffn(x, p, top_k: int, offset: int, quant, follow=None,
             margin: float = 0.0):
    """``shared(x)`` plus the held experts' part; also the experts chosen
    (S, top_k), the experts this reference chooses left to itself, how many
    pairs each held expert got, and ``need`` (S,): as
    ``reference_lm_trunk._moe_ffn``, for this router. The reference chooses
    the ``top_k`` largest of its own logits with ``margin`` added to the
    answer's choices (``follow``), and weighs by the softmax of its own
    logits over what it chose."""
    logits = router_logits(x, p["router/kernel"])
    _, own = lax.top_k(logits, top_k)
    idx, need = own, jnp.zeros(logits.shape[:1], F32)
    if follow is not None:
        n = logits.shape[-1]
        theirs = (follow[..., None] == jnp.arange(n)).any(-2)
        mine = (own[..., None] == jnp.arange(n)).any(-2)
        _, idx = lax.top_k(logits + theirs * margin, top_k)
        left_out = jnp.where(mine & ~theirs, logits, -jnp.inf).max(-1)
        instead = jnp.where(theirs & ~mine, logits, jnp.inf).min(-1)
        need = jnp.where(jnp.isfinite(left_out), left_out - instead, 0.0)
    weights = jax.nn.softmax(jnp.take_along_axis(logits, idx, axis=-1), -1)
    held = p["experts/gate"].shape[0]

    def one_expert(acc, e):
        w = jnp.where(idx == e + offset, weights, 0.0).sum(-1)
        y = _gated_mlp(x, p["experts/gate"][e], p["experts/up"][e],
                       p["experts/down"][e], quant)
        return acc + y * w[:, None], (idx == e + offset).sum()

    routed, counts = lax.scan(one_expert, jnp.zeros_like(x),
                              jnp.arange(held))
    shared = _gated_mlp(x, p["shared/gate/kernel"], p["shared/up/kernel"],
                        p["shared/down/kernel"], quant)
    return shared + routed, idx, own, counts, need


def trunk(bb: dict, x, model: dict, quant=None, routing=None, follow=None,
          margin: float = 0.0, rekernel=None):
    """The layers and the final norm: x (S, D) float32, the embedding
    already multiplied -> (S, D). ``routing``, ``follow`` and ``margin`` as
    ``reference_lm_trunk.trunk``'s. ``rekernel(path, h)`` -> a router
    kernel: whoever prepares weights may set an expert layer's (leaf
    ``path`` of ``bb``) on seeing this reference's own ``norm2`` outputs
    ``h`` (S, D) there, before the layer chooses; the layers after it see
    the result."""
    eps = float(model["rms_norm_eps"])
    r = float(model["residual_multiplier"])
    follow = list(follow) if follow is not None else None
    for i, (mixer, ffn) in enumerate(model["layers"]):
        p = _sub(bb, f"layers_{i}/")
        y = _rms_norm(x, p["norm1/weight"], eps)
        attn = _sub(p, "attn/")
        if mixer == "ssm":
            y = _ssm(y, attn, model["mamba_n_heads"], model["mamba_d_state"],
                     model["mamba_n_groups"], eps, quant)
        elif mixer == "gqa":
            y = _gqa(y, attn, model["num_heads"],
                     model["num_key_value_heads"],
                     float(model["attention_multiplier"]), quant)
        else:
            raise KeyError(f"this trunk has no {mixer!r} layer")
        x = x + r * y
        y = _rms_norm(x, p["norm2/weight"], eps)
        if ffn != "moe":
            raise KeyError(f"this trunk has no {ffn!r} feed-forward")
        if rekernel is not None:
            p["ffn/router/kernel"] = rekernel(
                f"layers_{i}/ffn/router/kernel", y)
        out, idx, own, counts, need = _moe_ffn(
            y, _sub(p, "ffn/"), model["num_experts_per_token"],
            int(model.get("expert_offset", 0)), quant,
            follow=jnp.asarray(follow.pop(0)) if follow else None,
            margin=float(margin))
        x = x + r * out
        if routing is not None:
            routing.append({"experts": np.asarray(idx),
                            "own": np.asarray(own),
                            "counts": np.asarray(counts),
                            "need": np.asarray(need)})
    return _rms_norm(x, bb["final_norm/weight"], eps)


def embed_tokens(bb: dict, image, model: dict, quant=None):
    """The patch embedding in the token embedding's place, times
    ``embedding_multiplier``: (S, S, 3) -> (1, h, w, D)."""
    return reference_lm_trunk.embed_tokens(bb, image, model, quant) * float(
        model["embedding_multiplier"])


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
