"""Plain reference of the detector on the SDAR-MoE trunk: float32,
``highest`` precision, one image at a time, no kernels, no sorting: a masked
softmax a head under the block-causal mask, the router as published, the
held experts one after another.

The trunk follows the model's ``config.json`` (``model_type`` ``sdar_moe``,
Qwen3-MoE's layer). Pre-norm, RMSNorm eps ``rms_norm_eps``
(``x rsqrt(mean(x^2) + eps) w``), SiLU, no bias anywhere
(``attention_bias`` false)::

    x <- x + attn(norm1(x))
    x <- x + moe(norm2(x))           every layer (decoder_sparse_step 1)

and after the last layer one more RMSNorm.

- **Attention**: ``q`` 32 heads, ``k``, ``v`` 4 heads of 128; an RMSNorm
  over each head's 128 dims on q and on k (Qwen3's ``q_norm`` / ``k_norm``,
  the same eps); then the default rotary (``theta^(-2i/128)``, pairing dim
  ``i`` with ``i + 64``, ``rotate_half``), from tables of its own; query head
  ``j`` reads key-value head ``j // 8`` (repeated here); scale ``128^-1/2``;
  a masked softmax a head under **block diffusion's mask**: query ``i`` sees
  key ``j`` where ``floor(j / L) <= floor(i / L)``, ``L`` the diffusion
  block (``model["diffusion_block"]``), built here from its own ``L``.
- **Experts**: ``p = softmax(h W_r)`` over all ``num_experts`` (float32);
  the ``num_experts_per_tok`` largest are chosen; their weights are
  ``p_chosen / sum p_chosen`` (``norm_topk_prob``); expert ``e`` is
  ``W_down,e (silu(W_gate,e h) * W_up,e h)``; no shared expert. What the
  experts that are not held would add is left out, as in the program
  (``model["experts_held"]``, ``model["expert_offset"]``).

Departures from the published model are the benchmark's, shared with the
program and the sibling trunks: the patch embedding stands in the token
embedding's place and the neck in the head's; a prefill of committed
patches under the block mask stands where the model's block-by-block
denoising would run (a committed context holds no mask token, so no noise
schedule acts on it).

Everything around the trunk is ``reference.py``'s and the stem,
``_rms_norm``, ``_linear`` and ``_gated_mlp`` are ``reference_lm_trunk``'s,
by import. It imports nothing of the program under test. The weights are the
benchmark's own flat ``{"a/b/c": array}`` dict in the type the program holds
them (bfloat16 in the trunk); each layer's function upcasts its own leaves,
so no more than one layer is float32 at a time and the forty-eight fit after
the program's state is freed. ``quant`` is the control, as in
``reference.py``: every matrix product's operands rounded.
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
from benchmarks.reference_lm_trunk import (_gated_mlp, _linear, _rms_norm,
                                           embed_tokens)

__all__ = ["forward_dense", "detect", "trunk", "embed_tokens",
           "router_logits"]

F32 = jnp.float32


def _rotary(t, theta: float):
    """``t`` (S, H, d): pair ``i`` of a head, dims ``(i, i + d / 2)``,
    turned by ``position * theta^(-2i/d)``; the angles from float64
    frequencies."""
    s, _, d = t.shape
    inv_freq = theta ** (-np.arange(d // 2, dtype=np.float64) * 2.0 / d)
    angle = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv_freq, F32)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = t[..., :d // 2], t[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def block_mask(s: int, block: int):
    """(S, S): query ``i`` sees key ``j`` where ``j // L <= i // L``."""
    pos = np.arange(s) // block
    return jnp.asarray(pos[None, :] <= pos[:, None])


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta",
                                             "eps", "block", "quant"))
def _gqa(x, p, heads: int, kv_heads: int, theta: float, eps: float,
         block: int, quant):
    """x (S, D), already normed -> (S, D): q and k normed a head and turned,
    a masked softmax a head under the block mask, the key-value heads
    repeated."""
    s = x.shape[0]
    q = _linear(x, p["q_proj/kernel"], quant).reshape(s, heads, -1)
    k = _linear(x, p["k_proj/kernel"], quant).reshape(s, kv_heads, -1)
    v = _linear(x, p["v_proj/kernel"], quant).reshape(s, kv_heads, -1)
    q = _rotary(_rms_norm(q, p["q_norm/weight"], eps), theta)
    k = _rotary(_rms_norm(k, p["k_norm/weight"], eps), theta)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scale = q.shape[-1] ** -0.5
    seen = block_mask(s, block)

    def one_head(t):
        q_h, k_h, v_h = t
        scores = _dot("qc,kc->qk", q_h, k_h, quant) * scale
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
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
    """The held experts' part, no shared expert; also the experts chosen
    (S, top_k), the experts this reference chooses left to itself, how many
    pairs each held expert got, and ``need`` (S,): as
    ``reference_granite_trunk._moe_ffn``, in logits, for the published
    router: the reference chooses the ``top_k`` largest of its own logits
    (the largest probabilities) with ``margin`` added to the answer's
    choices (``follow``), and weighs by its own softmax over all the experts,
    renormalised over what it chose."""
    logits = router_logits(x, p["router/kernel"])
    probs = jax.nn.softmax(logits, axis=-1)
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
    chosen = jnp.take_along_axis(probs, idx, axis=-1)
    weights = chosen / chosen.sum(-1, keepdims=True)
    held = p["experts/gate"].shape[0]

    def one_expert(acc, e):
        w = jnp.where(idx == e + offset, weights, 0.0).sum(-1)
        y = _gated_mlp(x, p["experts/gate"][e], p["experts/up"][e],
                       p["experts/down"][e], quant)
        return acc + y * w[:, None], (idx == e + offset).sum()

    routed, counts = lax.scan(one_expert, jnp.zeros_like(x),
                              jnp.arange(held))
    return routed, idx, own, counts, need


def trunk(bb: dict, x, model: dict, quant=None, routing=None, follow=None,
          margin: float = 0.0, rekernel=None):
    """The layers and the final norm: x (S, D) float32 -> (S, D).
    ``routing``, ``follow``, ``margin`` and ``rekernel`` as
    ``reference_granite_trunk.trunk``'s."""
    eps = float(model["rms_norm_eps"])
    follow = list(follow) if follow is not None else None
    for i, (mixer, ffn) in enumerate(model["layers"]):
        if (mixer, ffn) != ("gqa", "moe"):
            raise KeyError(f"this trunk has no {mixer!r} / {ffn!r} layer")
        p = _sub(bb, f"layers_{i}/")
        y = _rms_norm(x, p["norm1/weight"], eps)
        x = x + _gqa(y, _sub(p, "attn/"), model["num_heads"],
                     model["num_key_value_heads"], float(model["rope_theta"]),
                     eps, int(model["diffusion_block"]), quant)
        y = _rms_norm(x, p["norm2/weight"], eps)
        if rekernel is not None:
            p["ffn/router/kernel"] = rekernel(
                f"layers_{i}/ffn/router/kernel", y)
        out, idx, own, counts, need = _moe_ffn(
            y, _sub(p, "ffn/"), model["num_experts_per_token"],
            int(model.get("expert_offset", 0)), quant,
            follow=jnp.asarray(follow.pop(0)) if follow else None,
            margin=float(margin))
        x = x + out
        if routing is not None:
            routing.append({"experts": np.asarray(idx),
                            "own": np.asarray(own),
                            "counts": np.asarray(counts),
                            "need": np.asarray(need)})
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
