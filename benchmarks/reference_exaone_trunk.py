"""Plain reference of the detector on the K-EXAONE trunk: float32,
``highest`` precision, one image at a time, no kernels, no sorting: a masked
softmax a head (the sliding window written as a band of the S x S scores),
the router as published, the held experts one after another.

The trunk follows the model's ``config.json`` (``model_type``
``exaone_moe``) and the family's published layer (EXAONE 4.0, arXiv
2507.11407; transformers ``models/exaone4/modeling_exaone4.py``). A norm
*after* each sub-layer and none ahead of it, RMSNorm eps ``rms_norm_eps``
(``x rsqrt(mean(x^2) + eps) w``), SiLU, no bias anywhere::

    x <- x + post_attn_norm(attn(x))   attn by layer: "swa" | "gqa"
    x <- x + post_ffn_norm(ffn(x))     ffn: "dense" (layer 0) | "moe"

and after the last layer one more RMSNorm.

- **Attention**: ``q`` 64 heads, ``k``, ``v`` 8 heads of 128; an RMSNorm
  over each head's 128 dims on q and on k (``q_norm`` / ``k_norm``, the
  same eps); query head ``j`` reads key-value head ``j // 8`` (repeated
  here); scale ``128^-1/2``; a masked softmax a head.
  On a **sliding** layer (``swa``, ``layer_types`` ``sliding_attention``)
  q and k are then turned by the default rotary (``theta^(-2i/128)``,
  pairing dim ``i`` with ``i + 64``, ``rotate_half``) from tables of its
  own, and query ``i`` sees key ``j`` where ``i - W < j <= i``, ``W`` the
  ``sliding_window`` (128), built here as a band from its own ``W``. On a
  **full** layer (``gqa``, ``full_attention``) there is no rotary (the
  family's "global NoPE") and query ``i`` sees every ``j <= i``.
- **Dense feed-forward**: ``W_down (silu(W_gate h) * W_up h)``.
- **Experts**: ``s = sigmoid(h W_r)`` (float32); the ``num_experts_per_tok``
  experts with the largest ``s + b`` are chosen (``b`` the selection bias,
  DeepSeek-V3's ``noaux_tc``; ``n_group`` = ``topk_group`` = 1 choose from
  all); weights ``s`` of the chosen over their sum (``norm_topk_prob``) times
  ``routed_scaling_factor``; plus one shared expert of the experts' width.
  What the experts that are not held would add is left out, as in the
  program (``model["experts_held"]``, ``model["expert_offset"]``). This is
  ``reference_lm_trunk._moe_ffn``, by import.

Departures from the published model are the benchmark's, shared with the
program and the sibling trunks: the patch embedding stands in the token
embedding's place and the neck in the head's; the multi-token-prediction
module, which drafts text tokens, is on no path of a detector and is left
out.

Everything around the trunk is ``reference.py``'s and the stem, the router's
scores, ``_rms_norm``, ``_linear``, ``_dense_ffn`` and ``_moe_ffn`` are
``reference_lm_trunk``'s, the rotary ``reference_sdar_trunk``'s, by import.
It imports nothing of the program under test. The weights are the
benchmark's own flat ``{"a/b/c": array}`` dict in the type the program holds
them (bfloat16 in the trunk); each layer's function upcasts its own leaves,
so no more than one layer is float32 at a time. ``quant`` is the control, as
in ``reference.py``: every matrix product's operands rounded.
"""

from __future__ import annotations

import functools

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
from benchmarks.reference_sdar_trunk import _rotary

__all__ = ["forward_dense", "detect", "trunk", "embed_tokens",
           "router_scores", "band"]


def band(s: int, window) -> jnp.ndarray:
    """(S, S): query ``i`` sees key ``j`` where ``i - W < j <= i``; with no
    window, every ``j <= i``."""
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    seen = j <= i
    if window is not None:
        seen &= j > i - window
    return jnp.asarray(seen)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "window", "eps", "quant"))
def _attention(x, p, heads: int, kv_heads: int, theta, window, eps: float,
               quant):
    """x (S, D), the stream as it is (no norm ahead) -> (S, D): q and k
    normed a head, turned where ``theta`` is given, a masked softmax a head
    under :func:`band`, the key-value heads repeated."""
    s = x.shape[0]
    q = _linear(x, p["q_proj/kernel"], quant).reshape(s, heads, -1)
    k = _linear(x, p["k_proj/kernel"], quant).reshape(s, kv_heads, -1)
    v = _linear(x, p["v_proj/kernel"], quant).reshape(s, kv_heads, -1)
    q = _rms_norm(q, p["q_norm/weight"], eps)
    k = _rms_norm(k, p["k_norm/weight"], eps)
    if theta is not None:
        q, k = _rotary(q, theta), _rotary(k, theta)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scale = q.shape[-1] ** -0.5
    seen = band(s, window)

    def one_head(t):
        q_h, k_h, v_h = t
        scores = _dot("qc,kc->qk", q_h, k_h, quant) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return _dot("qk,kc->qc", probs, v_h, quant)

    o = lax.map(one_head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return _linear(o.transpose(1, 0, 2).reshape(s, -1), p["o_proj/kernel"],
                   quant)


def trunk(bb: dict, x, model: dict, quant=None, routing=None, follow=None,
          margin: float = 0.0, rebias=None):
    """The layers and the final norm: x (S, D) float32 -> (S, D).
    ``routing``, ``follow``, ``margin`` and ``rebias`` as
    ``reference_lm_trunk.trunk``'s; ``rebias`` sees the scores of the
    expert layer's own input, the stream (there is no norm ahead of it)."""
    eps = float(model["rms_norm_eps"])
    follow = list(follow) if follow is not None else None
    for i, (mixer, ffn) in enumerate(model["layers"]):
        if mixer not in ("swa", "gqa"):
            raise KeyError(f"this trunk has no {mixer!r} layer")
        p = _sub(bb, f"layers_{i}/")
        sliding = mixer == "swa"
        a = _attention(
            x, _sub(p, "attn/"), model["num_heads"],
            model["num_key_value_heads"],
            float(model["rope_theta"]) if sliding else None,
            int(model["sliding_window"]) if sliding else None, eps, quant)
        x = x + _rms_norm(a, p["post_attn_norm/weight"], eps)
        if ffn == "dense":
            f = _dense_ffn(x, _sub(p, "ffn/"), quant)
        else:
            if rebias is not None:
                p["ffn/router/bias"] = rebias(
                    f"layers_{i}/ffn/router/bias",
                    router_scores(x, p["ffn/router/kernel"]))
            f, idx, own, counts, need = _moe_ffn(
                x, _sub(p, "ffn/"), model["num_experts_per_token"],
                int(model.get("expert_offset", 0)),
                float(model["routed_scaling_factor"]), quant,
                follow=jnp.asarray(follow.pop(0)) if follow else None,
                margin=float(margin))
            if routing is not None:
                routing.append({"experts": np.asarray(idx),
                                "own": np.asarray(own),
                                "counts": np.asarray(counts),
                                "need": np.asarray(need)})
        x = x + _rms_norm(f, p["post_ffn_norm/weight"], eps)
    return _rms_norm(x, bb["final_norm/weight"], eps)


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
