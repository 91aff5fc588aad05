"""Operations and bytes of the Xing4.0 trunk as the detector's backbone,
from the configuration's shapes alone (``configs/xing4_fscd147.json``, key
``model``): the whole forward, and for each mechanism with a roofline the
least its algorithm needs, the same whatever implements it.

``forward_flops_per_image``: patch embedding, the layers as published (a
multiply and an add are 2; every product of ``reference_xing_trunk``,
Sinkhorn's and the softmax's element-wise work not counted), the neck, and
the matcher, heads and their projection as ``work.forward_flops_per_image``
counts them. An expert layer is counted at the pairs *expected here*.

``hc_mix_per_image``: a hyper-connected sub-layer's coefficient product
``2 n C (2 n + n^2)`` and its two mixes ``2 n C`` and ``2 n^2 C + 2 n C`` a
token; the streams read once for the coefficients and the pre-mix (a kernel
can hold a token's streams between the two) and once for the post-mix,
written once, the sub-layer's input written and its output read once, ``phi``
once a batch. ``mla_attn_per_image``: causal scores and values,
``2 (192 + 128) H (S + 1) / 2`` a token a layer; q, k, v in and o out once.
``moe_experts_per_image``: ``work_lm_trunk``'s definition at these sizes.
"""

from __future__ import annotations


def _sizes(model: dict, image_size: int) -> dict:
    grid = image_size // model["patch_size"]
    return {
        "s": grid * grid, "d": model["hidden_size"],
        "heads": model["num_heads"], "q_rank": model["q_lora_rank"],
        "qk": model["qk_nope_head_dim"] + model["qk_rope_head_dim"],
        "nope": model["qk_nope_head_dim"], "pe": model["qk_rope_head_dim"],
        "dv": model["v_head_dim"], "kv_rank": model["kv_lora_rank"],
        "n": model["hc_mult"], "dense": model["intermediate_size"],
        "width": model["moe_intermediate_size"],
        "router": model["router_experts"], "held": model["experts_held"],
        "top_k": model["num_experts_per_token"],
        "n_mla": sum(m == "mla" for m, _ in model["layers"]),
        "n_dense": sum(f == "dense" for _, f in model["layers"]),
        "n_moe": sum(f == "moe" for _, f in model["layers"]),
    }


def pairs_expected(z: dict) -> float:
    """Token-expert pairs an image brings to the held experts of a layer."""
    return z["s"] * z["top_k"] * z["held"] / z["router"]


def hc_flops_per_token(z: dict) -> float:
    """One hyper-connected sub-layer: coefficients, pre-mix, post-mix."""
    n, c = z["n"], z["d"]
    return (2.0 * n * c * (2 * n + n * n) + 2.0 * n * c
            + 2.0 * n * n * c + 2.0 * n * c)


def mla_attn_flops_per_token(z: dict) -> float:
    # causal: a token meets (S + 1) / 2 keys on average
    return 2.0 * (z["qk"] + z["dv"]) * z["heads"] * (z["s"] + 1) / 2.0


def mla_mixer_flops_per_token(z: dict) -> float:
    d, h = z["d"], z["heads"]
    proj = 2.0 * (d * z["q_rank"] + z["q_rank"] * h * z["qk"]
                  + d * (z["kv_rank"] + z["pe"])
                  + z["kv_rank"] * h * (z["nope"] + z["dv"])
                  + h * z["dv"] * d)
    return proj + mla_attn_flops_per_token(z)


def moe_ffn_flops_per_token(z: dict) -> float:
    expert = 6.0 * z["d"] * z["width"]
    return (2.0 * z["d"] * z["router"] + expert
            + expert * z["top_k"] * z["held"] / z["router"])


def trunk_flops_per_image(model: dict, image_size: int) -> float:
    z = _sizes(model, image_size)
    layers = z["n_mla"]
    per_token = (layers * (mla_mixer_flops_per_token(z)
                           + 2 * hc_flops_per_token(z))
                 + z["n_dense"] * 6.0 * z["d"] * z["dense"]
                 + z["n_moe"] * moe_ffn_flops_per_token(z))
    return z["s"] * per_token


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


def hc_mix_per_image(model: dict, image_size: int, batch: int) -> dict:
    """The 2 x layers hyper-connected sub-layers of an image; activations at
    2 bytes (the compute type), ``phi`` at 2 (the leaf's) once a batch."""
    z = _sizes(model, image_size)
    n, c = z["n"], z["d"]
    subs = 2 * len(model["layers"])
    per_token = (3 * n * c + 2 * c) * 2
    phi = n * c * (2 * n + n * n) * 2 / float(batch)
    return {"flops": subs * z["s"] * hc_flops_per_token(z),
            "bytes": float(subs * (z["s"] * per_token + phi))}


def mla_attn_per_image(model: dict, image_size: int) -> dict:
    z = _sizes(model, image_size)
    per_token = z["heads"] * (2 * z["qk"] + 2 * z["dv"]) * 2
    return {"flops": z["n_mla"] * z["s"] * mla_attn_flops_per_token(z),
            "bytes": float(z["n_mla"] * z["s"] * per_token)}


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
