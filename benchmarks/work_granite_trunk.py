"""Operations and bytes of the Granite 4.0-H trunk as the detector's
backbone, from the configuration's shapes alone
(``configs/granite4h_fscd147.json``, key ``model``): the whole forward, and
for each mechanism with a roofline the least its algorithm needs, the same
whatever implements it.

``forward_flops_per_image``: patch embedding, the layers as published (a
multiply and an add are 2; every product of ``reference_granite_trunk``,
the convolution, and the recurrence as its definition counts it; the
element-wise work of norms, gates and the softmax not counted), the neck,
and the matcher, heads and their projection as
``work.forward_flops_per_image`` counts them. An expert layer is counted at
the pairs *expected here*.

``ssd_scan_per_image``: **the recurrence's own work**, a token and head:
the state's decay ``P N``, its rank-one update ``2 P N`` and its read-out
``2 P N``, and ``D u`` ``2 P``; the bytes it must move: ``u`` in and ``y``
out in the compute type (2 bytes), ``B`` and ``C`` a group in the compute
type, ``Delta`` one float32 a head. Nothing in it depends on a chunk: a
chunked form multiplies more (the chunk's ``C B^T`` and mask) and a kernel
is held to the same yardstick. ``moe_experts_per_image``:
``work_lm_trunk``'s definition at these sizes.
"""

from __future__ import annotations


def _sizes(model: dict, image_size: int) -> dict:
    grid = image_size // model["patch_size"]
    return {
        "s": grid * grid, "d": model["hidden_size"],
        "ssm_heads": model["mamba_n_heads"], "p": model["mamba_d_head"],
        "n": model["mamba_d_state"], "g": model["mamba_n_groups"],
        "conv": model["mamba_d_conv"],
        "heads": model["num_heads"], "kv_heads": model["num_key_value_heads"],
        "hd": model["head_dim"],
        "width": model["intermediate_size"],
        "shared": model["shared_intermediate_size"],
        "router": model["router_experts"], "held": model["experts_held"],
        "top_k": model["num_experts_per_token"],
        "n_ssm": sum(m == "ssm" for m, _ in model["layers"]),
        "n_gqa": sum(m == "gqa" for m, _ in model["layers"]),
        "n_moe": sum(f == "moe" for _, f in model["layers"]),
    }


def pairs_expected(z: dict) -> float:
    """Token-expert pairs an image brings to the held experts of a layer."""
    return z["s"] * z["top_k"] * z["held"] / z["router"]


def ssd_scan_flops_per_token_head(z: dict) -> float:
    """``H = a H + Delta u B^T`` (P N + 2 P N), ``y = H C + D u``
    (2 P N + 2 P)."""
    return 5.0 * z["p"] * z["n"] + 2.0 * z["p"]


def ssm_mixer_flops_per_token(z: dict) -> float:
    inner = z["ssm_heads"] * z["p"]
    conv_dim = inner + 2 * z["g"] * z["n"]
    proj = 2.0 * z["d"] * (inner + conv_dim + z["ssm_heads"]) \
        + 2.0 * inner * z["d"]
    return (proj + 2.0 * z["conv"] * conv_dim
            + z["ssm_heads"] * ssd_scan_flops_per_token_head(z))


def gqa_mixer_flops_per_token(z: dict) -> float:
    h, hkv, hd = z["heads"], z["kv_heads"], z["hd"]
    proj = 2.0 * z["d"] * (h + 2 * hkv) * hd + 2.0 * h * hd * z["d"]
    # causal: a token meets (S + 1) / 2 keys on average
    return proj + 2.0 * 2 * hd * h * (z["s"] + 1) / 2.0


def moe_ffn_flops_per_token(z: dict) -> float:
    expert = 6.0 * z["d"] * z["width"]
    return (2.0 * z["d"] * z["router"] + 6.0 * z["d"] * z["shared"]
            + expert * z["top_k"] * z["held"] / z["router"])


def trunk_flops_per_image(model: dict, image_size: int) -> float:
    z = _sizes(model, image_size)
    per_token = (z["n_ssm"] * ssm_mixer_flops_per_token(z)
                 + z["n_gqa"] * gqa_mixer_flops_per_token(z)
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


def ssd_scan_per_image(model: dict, image_size: int) -> dict:
    z = _sizes(model, image_size)
    token_heads = z["s"] * z["ssm_heads"]
    # u in and y out in the compute type, Delta one float32 a head; B and C
    # once a group
    per_token = (z["ssm_heads"] * (z["p"] * (2 + 2) + 4)
                 + z["g"] * 2 * z["n"] * 2)
    return {"flops": z["n_ssm"] * token_heads
            * ssd_scan_flops_per_token_head(z),
            "bytes": float(z["n_ssm"] * z["s"] * per_token)}


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
