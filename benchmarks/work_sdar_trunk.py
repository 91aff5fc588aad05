"""Operations and bytes of the SDAR-MoE trunk as the detector's backbone,
from the configuration's shapes alone (``configs/sdar30b_fscd147.json``,
key ``model``): the whole forward, and for each mechanism with a roofline
the least its algorithm needs, the same whatever implements it.

``forward_flops_per_image``: patch embedding, the layers as published (a
multiply and an add are 2; every product of ``reference_sdar_trunk``; norms,
rotary and the softmax not counted), the neck, and the matcher, heads and
their projection as ``work.forward_flops_per_image`` counts them. An expert
layer is counted at the pairs *expected here*.

``gqa_attn_per_image``: **the block-causal scores and values alone**, what
the kernel under ``attn/softmax/`` does: a query ``i`` meets the keys of the
diffusion blocks up to its own, ``(i // L + 1) L``, so a layer's pairs are
``S (S + L) / 2`` a head and the two products ``2 d H S (S + L)`` FLOPs; as
bytes, q, k and v in and o out once at 2 bytes. ``moe_experts_per_image``:
``work_lm_trunk``'s definition at these sizes.
"""

from __future__ import annotations


def _sizes(model: dict, image_size: int) -> dict:
    grid = image_size // model["patch_size"]
    return {
        "s": grid * grid, "d": model["hidden_size"],
        "heads": model["num_heads"], "kv_heads": model["num_key_value_heads"],
        "hd": model["head_dim"], "block": model["diffusion_block"],
        "width": model["moe_intermediate_size"],
        "router": model["router_experts"], "held": model["experts_held"],
        "top_k": model["num_experts_per_token"],
        "n_layers": len(model["layers"]),
    }


def pairs_expected(z: dict) -> float:
    """Token-expert pairs an image brings to the held experts of a layer."""
    return z["s"] * z["top_k"] * z["held"] / z["router"]


def gqa_scores_values_flops_per_token(z: dict) -> float:
    """A token meets (S + L) / 2 keys on average under the block mask."""
    return 2.0 * 2 * z["hd"] * z["heads"] * (z["s"] + z["block"]) / 2.0


def gqa_mixer_flops_per_token(z: dict) -> float:
    h, hkv, hd = z["heads"], z["kv_heads"], z["hd"]
    proj = 2.0 * z["d"] * (h + 2 * hkv) * hd + 2.0 * h * hd * z["d"]
    return proj + gqa_scores_values_flops_per_token(z)


def moe_ffn_flops_per_token(z: dict) -> float:
    expert = 6.0 * z["d"] * z["width"]
    return 2.0 * z["d"] * z["router"] + expert * pairs_expected(z) / z["s"]


def trunk_flops_per_image(model: dict, image_size: int) -> float:
    z = _sizes(model, image_size)
    return z["s"] * z["n_layers"] * (gqa_mixer_flops_per_token(z)
                                     + moe_ffn_flops_per_token(z))


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


def gqa_attn_per_image(model: dict, image_size: int) -> dict:
    """The block-causal scores and values of every layer: FLOPs as
    :func:`gqa_scores_values_flops_per_token`; bytes q, k, v in and o out
    once, at 2 bytes."""
    z = _sizes(model, image_size)
    q = z["s"] * z["heads"] * z["hd"] * 2
    kv = z["s"] * z["kv_heads"] * z["hd"] * 2
    return {"flops": (z["n_layers"] * z["s"]
                      * gqa_scores_values_flops_per_token(z)),
            "bytes": float(z["n_layers"] * (2 * q + 2 * kv))}


def moe_experts_per_image(model: dict, image_size: int, batch: int,
                          pairs=None) -> dict:
    """``pairs``: the token-expert pairs an image brought to a layer's held
    experts, where the run counted them; else the expected ones."""
    z = _sizes(model, image_size)
    pairs = pairs_expected(z) if pairs is None else float(pairs)
    weights = z["held"] * 3 * z["d"] * z["width"] * 2 / float(batch)
    acts = pairs * 2 * z["d"] * 2
    return {"flops": z["n_layers"] * pairs * 6.0 * z["d"] * z["width"],
            "bytes": float(z["n_layers"] * (weights + acts))}
