"""Operations and bytes of the K-EXAONE trunk as the detector's backbone,
from the configuration's shapes alone (``configs/kexaone_fscd147.json``,
key ``model``): the whole forward, and for each mechanism with a roofline
the least its algorithm needs, the same whatever implements it.

``forward_flops_per_image``: patch embedding, the layers as published (a
multiply and an add are 2; every product of ``reference_exaone_trunk``;
norms, rotary and the softmax not counted), the neck, and the matcher,
heads and their projection as ``work.forward_flops_per_image`` counts them.
The sliding layers' scores and values are counted **over the window**, the
full layer's over the causal triangle. An expert layer is counted at the
pairs *expected here*.

``swa_attn_per_image``: **the sliding window's scores and values alone**,
what the kernel under ``attn/softmax/`` of a sliding layer does: query ``i``
meets ``min(i + 1, W)`` keys, so a layer's two products are
``4 d H sum_i min(i + 1, W)`` FLOPs; as bytes, q, k and v in and o out
once at 2 bytes. At the cell's sizes the bytes bound it. It is the same
whatever implements the window: a form that multiplies keys outside it
reads low. ``gqa_attn_per_image``: the full layer's causal scores and
values, ``work_sdar_trunk``'s definition at a diffusion block of 1.
``moe_experts_per_image``: ``work_lm_trunk``'s definition at these sizes.
"""

from __future__ import annotations


def _sizes(model: dict, image_size: int) -> dict:
    grid = image_size // model["patch_size"]
    kinds = [mixer for mixer, _ in model["layers"]]
    ffns = [ffn for _, ffn in model["layers"]]
    return {
        "s": grid * grid, "d": model["hidden_size"],
        "heads": model["num_heads"], "kv_heads": model["num_key_value_heads"],
        "hd": model["head_dim"], "window": model["sliding_window"],
        "dense": model["intermediate_size"],
        "width": model["moe_intermediate_size"],
        "router": model["router_experts"], "held": model["experts_held"],
        "top_k": model["num_experts_per_token"],
        "n_swa": kinds.count("swa"), "n_gqa": kinds.count("gqa"),
        "n_dense": ffns.count("dense"), "n_moe": ffns.count("moe"),
    }


def pairs_expected(z: dict) -> float:
    """Token-expert pairs an image brings to the held experts of a layer."""
    return z["s"] * z["top_k"] * z["held"] / z["router"]


def _keys_met(z: dict, window) -> float:
    """Query-key pairs of a layer: ``sum_i min(i + 1, W)``, or with no
    window the causal triangle."""
    s = z["s"]
    w = s if window is None else min(window, s)
    return w * (w + 1) / 2.0 + (s - w) * w


def _scores_values_flops(z: dict, window) -> float:
    return 2.0 * 2 * z["hd"] * z["heads"] * _keys_met(z, window)


def _projection_flops_per_token(z: dict) -> float:
    h, hkv, hd = z["heads"], z["kv_heads"], z["hd"]
    return 2.0 * z["d"] * (h + 2 * hkv) * hd + 2.0 * h * hd * z["d"]


def trunk_flops_per_image(model: dict, image_size: int) -> float:
    z = _sizes(model, image_size)
    s, d = z["s"], z["d"]
    fl = s * (z["n_swa"] + z["n_gqa"]) * _projection_flops_per_token(z)
    fl += z["n_swa"] * _scores_values_flops(z, z["window"])
    fl += z["n_gqa"] * _scores_values_flops(z, None)
    fl += z["n_dense"] * s * 6.0 * d * z["dense"]
    routed = 6.0 * d * z["width"] * pairs_expected(z)
    fl += z["n_moe"] * (s * (2.0 * d * z["router"] + 6.0 * d * z["width"])
                        + routed)
    return fl


def forward_flops_per_image(model: dict, image_size: int,
                            template_cells: float) -> float:
    z = _sizes(model, image_size)
    s, d, oc = z["s"], z["d"], model["out_chans"]
    fl = s * (model["patch_size"] ** 2 * 3) * d * 2.0
    fl += trunk_flops_per_image(model, image_size)
    fl += s * d * oc * 2.0 + s * 9.0 * oc ** 2 * 2
    s_up = s * (4 if model["feature_upsample"] else 1)
    emb = model["emb_dim"]
    fl += s_up * oc * emb * 2.0
    fl += s_up * emb * float(template_cells) * 2.0
    dec = emb * (2 if model["fusion"] else 1)
    fl += (2 * model["decoder_num_layer"] * s_up
           * model["decoder_kernel_size"] ** 2 * dec * dec * 2.0)
    fl += s_up * dec * 5 * 2.0
    return fl


def _attn_bytes(z: dict) -> float:
    """q, k, v in and o out once, at 2 bytes."""
    q = z["s"] * z["heads"] * z["hd"] * 2
    kv = z["s"] * z["kv_heads"] * z["hd"] * 2
    return float(2 * q + 2 * kv)


def swa_attn_per_image(model: dict, image_size: int) -> dict:
    """The sliding layers' scores and values over their windows."""
    z = _sizes(model, image_size)
    return {"flops": z["n_swa"] * _scores_values_flops(z, z["window"]),
            "bytes": z["n_swa"] * _attn_bytes(z)}


def gqa_attn_per_image(model: dict, image_size: int) -> dict:
    """The full layers' causal scores and values."""
    z = _sizes(model, image_size)
    return {"flops": z["n_gqa"] * _scores_values_flops(z, None),
            "bytes": z["n_gqa"] * _attn_bytes(z)}


def moe_experts_per_image(model: dict, image_size: int, batch: int,
                          pairs=None) -> dict:
    """``pairs``: the token-expert pairs an image brought to a layer's held
    experts, where the run counted them; else the expected ones."""
    z = _sizes(model, image_size)
    pairs = pairs_expected(z) if pairs is None else float(pairs)
    weights = z["held"] * 3 * z["d"] * z["width"] * 2 / float(batch)
    acts = pairs * 2 * z["d"] * 2
    return {"flops": z["n_moe"] * pairs * 6.0 * z["d"] * z["width"],
            "bytes": float(z["n_moe"] * (weights + acts))}
