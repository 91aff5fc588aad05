"""Plain reference of the detector: float32, ``highest`` precision, one image
at a time, no kernels, no batching, no static template capacity.

It follows the published description (SAM's image encoder, ``sam_ViT.py``;
the TMR matcher and heads, ``template_matching.py``, ``regression_head.py``;
``Get_pred_boxes`` and torchvision's ``nms``) and imports nothing of the
program under test. Its weights are the benchmark's own (``weights.py``),
handed over as a flat ``{"a/b/c": array}`` dict.

Departures from the published code, each for the plain form only: NHWC
layout; the exemplar's RoIAlign is written out as explicit bilinear samples
on the host; the correlation is taken on the host by the correlation theorem
in float64; greedy NMS is a loop in numpy.

``quant`` puts the reference in the program's place at the nearest precision
below the one the configurations state (bfloat16): every matrix product and
convolution takes its two operands rounded to fp8 (e4m3, per-tensor scale) or
int8. That is the control of ``correct``: it has to come out as not correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST


# ------------------------------------------------------------ precision
def _fake_quant(x, mode):
    if mode is None:
        return x
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if mode == "fp8":
        s = amax / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    if mode == "int8":
        s = amax / 127.0
        return jnp.round(x / s) * s
    raise ValueError(f"unknown control precision {mode!r}")


def _dot(eq, a, b, quant):
    return jnp.einsum(eq, _fake_quant(a, quant), _fake_quant(b, quant),
                      precision=HI, preferred_element_type=jnp.float32)


def _conv(x, w, quant, stride=1, padding="VALID", groups=1):
    return lax.conv_general_dilated(
        _fake_quant(x, quant), _fake_quant(w, quant), (stride, stride),
        padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=HI,
        preferred_element_type=jnp.float32)


# --------------------------------------------------------------- pieces
def _layer_norm(x, scale, bias, eps=1e-6):
    u = x.mean(-1, keepdims=True)
    v = ((x - u) ** 2).mean(-1, keepdims=True)
    return (x - u) / jnp.sqrt(v + eps) * scale + bias


def interp_matrix(n_out: int, n_in: int) -> np.ndarray:
    """(n_out, n_in) weights of 1-D linear resampling with half-pixel
    centres and clamped edges: ``F.interpolate(align_corners=False)``."""
    m = np.zeros((n_out, n_in), np.float32)
    for i in range(n_out):
        s = min(max((i + 0.5) * n_in / n_out - 0.5, 0.0), n_in - 1.0)
        lo = int(math.floor(s))
        hi = min(lo + 1, n_in - 1)
        m[i, lo] += 1.0 - (s - lo)
        m[i, hi] += s - lo
    return m


def _rel_table(rel_pos, size: int):
    """(size, size, head_dim): ``get_rel_pos`` for equal query and key
    sizes, the table resampled when its length is not ``2 * size - 1``."""
    want = 2 * size - 1
    if rel_pos.shape[0] != want:
        rel_pos = jnp.einsum("ol,lc->oc", interp_matrix(want, rel_pos.shape[0]),
                             rel_pos, precision=HI)
    idx = np.arange(size)[:, None] - np.arange(size)[None, :] + (size - 1)
    return rel_pos[idx]


def _attention(x, p, heads: int, quant):
    """x (n, h, w, dim): n windows, or one whole image."""
    n, h, w, dim = x.shape
    hd = dim // heads
    qkv = _dot("nhwc,cd->nhwd", x, p["qkv/kernel"], quant) + p["qkv/bias"]
    qkv = qkv.reshape(n, h * w, 3, heads, hd)
    q, k, v = (qkv[:, :, i].transpose(0, 2, 1, 3) for i in range(3))
    rh = _rel_table(p["rel_pos_h"], h)
    rw = _rel_table(p["rel_pos_w"], w)

    def one_head(qkv_h):
        qh, kh, vh = qkv_h  # (n, hw, hd)
        s = _dot("nqc,nkc->nqk", qh * hd ** -0.5, kh, quant)
        r_q = qh.reshape(n, h, w, hd)
        rel_h = jnp.einsum("nhwc,hkc->nhwk", r_q, rh, precision=HI)
        rel_w = jnp.einsum("nhwc,wkc->nhwk", r_q, rw, precision=HI)
        s = s.reshape(n, h, w, h, w) + rel_h[..., :, None] + rel_w[..., None, :]
        s = jax.nn.softmax(s.reshape(n, h * w, h * w), axis=-1)
        return _dot("nqk,nkc->nqc", s, vh, quant)

    out = lax.map(one_head, tuple(t.transpose(1, 0, 2, 3) for t in (q, k, v)))
    out = out.transpose(1, 2, 0, 3).reshape(n, h, w, dim)
    return _dot("nhwc,cd->nhwd", out, p["proj/kernel"], quant) + p["proj/bias"]


@functools.partial(jax.jit, static_argnames=("heads", "window", "quant"))
def _block(x, p, heads: int, window: int, quant):
    """One transformer block on one image: x (1, h, w, dim)."""
    _, h, w, dim = x.shape
    y = _layer_norm(x, p["norm1/scale"], p["norm1/bias"])
    if window:
        ph, pw = (-h) % window, (-w) % window
        y = jnp.pad(y, ((0, 0), (0, ph), (0, pw), (0, 0)))
        gh, gw = (h + ph) // window, (w + pw) // window
        y = y.reshape(gh, window, gw, window, dim).transpose(0, 2, 1, 3, 4)
        y = y.reshape(gh * gw, window, window, dim)
    y = _attention(y, {k[5:]: v for k, v in p.items() if k.startswith("attn/")},
                   heads, quant)
    if window:
        y = y.reshape(gh, gw, window, window, dim).transpose(0, 2, 1, 3, 4)
        y = y.reshape(1, gh * window, gw * window, dim)[:, :h, :w]
    x = x + y
    y = _layer_norm(x, p["norm2/scale"], p["norm2/bias"])
    y = _dot("nhwc,cd->nhwd", y, p["mlp/lin1/kernel"], quant) + p["mlp/lin1/bias"]
    y = jax.nn.gelu(y, approximate=False)
    y = _dot("nhwc,cd->nhwd", y, p["mlp/lin2/kernel"], quant) + p["mlp/lin2/bias"]
    return x + y


@functools.partial(jax.jit, static_argnames=("patch", "quant"))
def _embed(image, p, patch: int, quant):
    x = _conv(image[None], p["patch_embed/kernel"], quant, stride=patch)
    x = x + p["patch_embed/bias"]
    pos = p["pos_embed"]
    h, w = x.shape[1], x.shape[2]
    if pos.shape[1:3] != (h, w):
        pos = jnp.einsum("oh,bhwc,pw->bopc", interp_matrix(h, pos.shape[1]),
                         pos, interp_matrix(w, pos.shape[2]), precision=HI)
    return x + pos


@functools.partial(jax.jit, static_argnames=("upsample", "quant"))
def _neck_and_project(x, p, proj_w, proj_b, upsample: bool, quant):
    x = _conv(x, p["neck_0/kernel"], quant)
    x = _layer_norm(x, p["neck_1/weight"], p["neck_1/bias"])
    x = _conv(x, p["neck_2/kernel"], quant, padding=((1, 1), (1, 1)))
    x = _layer_norm(x, p["neck_3/weight"], p["neck_3/bias"])
    if upsample:
        _, h, w, _ = x.shape
        x = jnp.einsum("oh,bhwc,pw->bopc", interp_matrix(2 * h, h), x,
                       interp_matrix(2 * w, w), precision=HI)
    return _conv(x, proj_w, quant) + proj_b


def _fake_quant_host(x: np.ndarray, mode) -> np.ndarray:
    if mode is None:
        return x
    import ml_dtypes

    amax = max(float(np.abs(x).max()), 1e-30)
    if mode == "fp8":
        s = amax / 448.0
        return (x / s).astype(ml_dtypes.float8_e4m3fn).astype(np.float64) * s
    s = amax / 127.0
    return np.round(x / s) * s


def _correlate(fp: np.ndarray, template: np.ndarray, quant) -> np.ndarray:
    """Depthwise VALID correlation of fp (H, W, C) with the exemplar's own
    template (ht, wt, C), over ht * wt, zero-padded back to (H, W): on the
    host in float64, by the correlation theorem, so that no template size
    compiles a program of its own (``template_matching.py:23-41``)."""
    hh, ww, _ = fp.shape
    ht, wt, _ = template.shape
    f = _fake_quant_host(fp.astype(np.float64), quant)
    t = _fake_quant_host(template.astype(np.float64), quant)
    shape = (hh + ht - 1, ww + wt - 1)
    full = np.fft.irfft2(
        np.fft.rfft2(f, shape, axes=(0, 1))
        * np.conj(np.fft.rfft2(t, shape, axes=(0, 1))), shape, axes=(0, 1))
    valid = full[:hh - ht + 1, :ww - wt + 1] / (ht * wt + 1e-14)
    out = np.zeros(fp.shape, np.float32)
    out[ht // 2:ht // 2 + valid.shape[0],
        wt // 2:wt // 2 + valid.shape[1]] = valid
    return out


@functools.partial(jax.jit, static_argnames=("layers", "quant"))
def _decode_heads(f_cat, p, layers: int, quant):
    def stack(name, x):
        for i in range(layers):
            k = p[f"{name}/conv_{i}/kernel"]
            pad = (k.shape[0] - 1) // 2
            x = _conv(x, k, quant, padding=((pad, pad), (pad, pad)))
            x = x + p[f"{name}/conv_{i}/bias"]
            x = jnp.where(x >= 0, x, 0.01 * x)
        return x

    f_obj = stack("decoder_o_0", f_cat)
    obj = _conv(f_obj, p["objectness_head_0/conv/kernel"], quant)
    obj = obj + p["objectness_head_0/conv/bias"]
    f_box = stack("decoder_b_0", f_cat)
    reg = _conv(f_box, p["ltrbs_head_0/conv/kernel"], quant)
    reg = reg + p["ltrbs_head_0/conv/bias"]
    return obj[0, :, :, 0], reg[0]


# ---------------------------------------------------- exemplar template
def template_size(exemplar, hh: int, ww: int):
    """Clipped feature-space box and the odd template size
    (``template_matching.py:55-73``)."""
    x1, y1, x2, y2 = (min(max(float(v), 0.0), 1.0) for v in exemplar)
    x1, x2, y1, y2 = x1 * ww, x2 * ww, y1 * hh, y2 * hh
    wt = math.ceil(x2) - math.floor(x1)
    ht = math.ceil(y2) - math.floor(y1)
    wt, ht = max(wt - (wt % 2 == 0), 1), max(ht - (ht % 2 == 0), 1)
    return (x1, y1, x2, y2), (ht, wt)


def _sample_axis(start, length, n_out, size):
    """RoIAlign's sample points along one axis, as (n_out, grid, size)
    bilinear weight rows (torchvision, ``aligned=True``, adaptive grid)."""
    grid = max(int(math.ceil(length / n_out)), 1)
    rows = np.zeros((n_out, grid, size), np.float64)
    for i in range(n_out):
        for g in range(grid):
            pos = start + (length / n_out) * (i + (g + 0.5) / grid)
            if pos < -1.0 or pos > size:
                continue
            pos = max(pos, 0.0)
            lo = int(pos)
            if lo >= size - 1:
                lo = hi = size - 1
                frac = 0.0
            else:
                hi, frac = lo + 1, pos - lo
            rows[i, g, lo] += 1.0 - frac
            rows[i, g, hi] += frac
    return rows.mean(axis=1)


def roi_align_template(fp: np.ndarray, exemplar) -> np.ndarray:
    """fp (H, W, C) -> (ht, wt, C): the exemplar region pooled to its own
    odd size."""
    hh, ww, _ = fp.shape
    (x1, y1, x2, y2), (ht, wt) = template_size(exemplar, hh, ww)
    ay = _sample_axis(y1 - 0.5, y2 - y1, ht, hh)
    ax = _sample_axis(x1 - 0.5, x2 - x1, wt, ww)
    return np.einsum("yh,hwc,xw->yxc", ay, fp.astype(np.float64), ax
                     ).astype(np.float32)


# -------------------------------------------------------------- forward
def _sub(flat: dict, prefix: str) -> dict:
    n = len(prefix)
    return {k[n:]: v for k, v in flat.items() if k.startswith(prefix)}


def forward_dense(flat: dict, image, exemplar, model: dict, quant=None):
    """One image (S, S, 3) and its exemplar box (4,) -> objectness logits
    (H, W) and ltrb regressions (H, W, 4), float32 numpy.

    ``model`` holds the configuration's sizes: ``num_heads``,
    ``global_attn_indexes``, ``window_size``, ``patch_size``, ``depth``,
    ``feature_upsample``, ``fusion``, ``decoder_num_layer``."""
    with jax.default_matmul_precision("highest"):
        bb = _sub(flat, "backbone/")
        x = _embed(jnp.asarray(image, jnp.float32), bb, model["patch_size"],
                   quant)
        for i in range(model["depth"]):
            window = 0 if i in model["global_attn_indexes"] else \
                model["window_size"]
            x = _block(x, _sub(bb, f"blocks_{i}/"), model["num_heads"],
                       window, quant)
        fp = _neck_and_project(x, bb, flat["input_proj_0/kernel"],
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


# ----------------------------------------------------------------- tail
_KERNELS = np.array([
    [[1, 1, 1], [1, 1, 1], [1, 1, 1]],  # full
    [[0, 0, 0], [0, 1, 0], [0, 0, 0]],  # point
    [[0, 1, 0], [0, 1, 0], [0, 1, 0]],  # column
    [[0, 0, 0], [1, 1, 1], [0, 0, 0]],  # row
    [[0, 1, 0], [1, 1, 1], [0, 1, 0]],  # cross
], bool)


def peak_kernel(ex_h: float, ex_w: float, hh: int, ww: int) -> np.ndarray:
    """``adaptive_kernel_generater`` (TM_utils.py:363-377)."""
    nh, nw = 1.0 / hh, 1.0 / ww
    if ex_h >= 3 * nh and ex_w >= 3 * nw:
        return _KERNELS[0]
    if ex_h < 2 * nh and ex_w < 2 * nw:
        return _KERNELS[1]
    if ex_h < 2 * nh:
        return _KERNELS[2]
    if ex_w < 2 * nw:
        return _KERNELS[3]
    return _KERNELS[4]


def neighbour_max(p: np.ndarray, kernel: np.ndarray) -> np.ndarray:
    """Largest value under the kernel's positions other than the centre
    (zero padding, as ``F.unfold`` gives)."""
    hh, ww = p.shape
    pad = np.pad(p, 1)
    out = np.full_like(p, -np.inf)
    for dy in range(3):
        for dx in range(3):
            if kernel[dy, dx] and (dy, dx) != (1, 1):
                out = np.maximum(out, pad[dy:dy + hh, dx:dx + ww])
    return out


def clipped_extent(exemplar):
    x1, y1, x2, y2 = (min(max(float(v), 0.0), 1.0) for v in exemplar)
    return x2 - x1, y2 - y1


def decode_boxes(reg: np.ndarray, exemplar) -> np.ndarray:
    """(H, W, 4) xyxy normalized (TM_utils.py:264-278)."""
    hh, ww, _ = reg.shape
    ew, eh = clipped_extent(exemplar)
    xs, ys = np.meshgrid(np.arange(ww, dtype=np.float32) / ww,
                         np.arange(hh, dtype=np.float32) / hh)
    cx, cy = xs + reg[..., 0] * ew, ys + reg[..., 1] * eh
    bw, bh = np.exp(reg[..., 2]) * ew, np.exp(reg[..., 3]) * eh
    return np.stack([cx - bw / 2, cy - bh / 2, cx + bw / 2, cy + bh / 2], -1)


def iou_one_to_many(box: np.ndarray, boxes: np.ndarray) -> np.ndarray:
    lt = np.maximum(box[:2], boxes[:, :2])
    rb = np.minimum(box[2:], boxes[:, 2:])
    wh = np.clip(rb - lt, 0.0, None)
    inter = wh[:, 0] * wh[:, 1]
    area = lambda b: (b[..., 2] - b[..., 0]) * (b[..., 3] - b[..., 1])
    union = area(box) + area(boxes) - inter
    return np.where(union > 0, inter / np.maximum(union, 1e-30), 0.0)


def detect(obj: np.ndarray, reg: np.ndarray, exemplar, cls_threshold: float,
           iou_threshold: float, max_detections: int) -> dict:
    """``Get_pred_boxes`` + greedy NMS on one image's dense maps: the kept
    detections as ``boxes`` (n, 4), ``scores`` (n,), ``cells`` (n,) flat
    indices, score-descending."""
    hh, ww = obj.shape
    p = 1.0 / (1.0 + np.exp(-obj.astype(np.float64))).astype(np.float32)
    ew, eh = clipped_extent(exemplar)
    peak = p >= neighbour_max(p, peak_kernel(eh, ew, hh, ww))
    cells = np.flatnonzero((peak & (p >= cls_threshold)).reshape(-1))
    order = np.argsort(-p.reshape(-1)[cells], kind="stable")
    cells = cells[order][:max_detections]
    scores = p.reshape(-1)[cells]
    boxes = decode_boxes(reg, exemplar).reshape(-1, 4)[cells]
    keep = np.ones(len(cells), bool)
    for i in range(len(cells)):
        if keep[i]:
            later = np.arange(len(cells)) > i
            keep &= ~(later & (iou_one_to_many(boxes[i], boxes)
                               > iou_threshold))
    return {"boxes": boxes[keep], "scores": scores[keep], "cells": cells[keep]}
