"""Operations and bytes that the algorithm needs, from the configuration's
shapes alone: never from ``cost_analysis()`` of whatever was compiled, never
from a kernel's name. The same work whatever implements it, so a share of a
peak computed from these cannot be raised by counting more.

``forward_flops_per_image`` is a copy of the arithmetic of the program's
``obs/devtime.py:forward_tflops_per_image`` (sound; the yardstick must not
move with the program), with the correlation counted at the exemplar's own
template size instead of a padded capacity.
"""

from __future__ import annotations

import json
import os


def shapes_of(model: dict, image_size: int) -> dict:
    """The sizes every count below is made from."""
    grid = image_size // model["patch_size"]
    win = model["window_size"]
    pad = -(-grid // win) * win
    return {
        "grid": grid, "s": grid * grid, "s_pad": pad * pad, "win": win,
        "d": model["embed_dim"], "depth": model["depth"],
        "heads": model["num_heads"],
        "n_global": len(model["global_attn_indexes"]),
        "patch": model["patch_size"], "out_chans": model["out_chans"],
        "emb": model["emb_dim"], "fusion": bool(model["fusion"]),
        "up": 2 if model["feature_upsample"] else 1,
        "dec_layers": model["decoder_num_layer"],
        "dec_k": model["decoder_kernel_size"],
    }


def global_attn_flops_per_block(z: dict) -> float:
    """qkv and proj (8 S D^2), score and value products (4 S^2 D), and the
    two decomposed rel-pos products (2 S grid D each)."""
    s, d, grid = z["s"], z["d"], z["grid"]
    return 8.0 * s * d * d + 4.0 * s * s * d + 2 * (2.0 * s * grid * d)


def global_attn_bytes_per_block(z: dict, act_bytes: int = 2,
                                weight_bytes: int = 4) -> float:
    """The least traffic of one global ``attn`` module: its weights once, the
    input read, q, k and v written and read, the attention output written and
    read, the module's output written. Scores are never counted: a blocked
    softmax keeps them on the chip."""
    s, d, grid = z["s"], z["d"], z["grid"]
    weights = (4 * d * d + 4 * d) * weight_bytes
    rel = 2 * (2 * grid - 1) * (d // z["heads"]) * weight_bytes
    acts = (1 + 2 * 3 + 2 + 1) * s * d * act_bytes
    return float(weights + rel + acts)


def global_attn_per_image(model: dict, image_size: int) -> dict:
    z = shapes_of(model, image_size)
    return {"flops": z["n_global"] * global_attn_flops_per_block(z),
            "bytes": z["n_global"] * global_attn_bytes_per_block(z)}


def forward_flops_per_image(model: dict, image_size: int,
                            template_cells: float) -> float:
    """Forward FLOPs (multiply + add = 2) of one image through the whole
    detector. Windowed blocks count the padded grid: SAM pads the grid to a
    multiple of the window and projects the padded tokens."""
    z = shapes_of(model, image_size)
    s, s_pad, d, win = z["s"], z["s_pad"], z["d"], z["win"]
    n_g, n_w = z["n_global"], z["depth"] - z["n_global"]
    fl = s * (z["patch"] ** 2 * 3) * d * 2.0
    fl += z["depth"] * s * 8.0 * d * d * 2
    fl += n_g * global_attn_flops_per_block(z)
    fl += n_w * (s_pad * 4.0 * d * d * 2 + 2.0 * s_pad * win * win * d * 2
                 + 2 * s_pad * win * d * 2.0)
    fl += s * d * z["out_chans"] * 2.0 + s * 9.0 * z["out_chans"] ** 2 * 2
    s_up = s * z["up"] ** 2
    fl += s_up * z["out_chans"] * z["emb"] * 2.0
    fl += s_up * z["emb"] * float(template_cells) * 2.0
    dec = z["emb"] * (2 if z["fusion"] else 1)
    fl += 2 * z["dec_layers"] * s_up * z["dec_k"] ** 2 * dec * dec * 2.0
    fl += s_up * dec * 5 * 2.0
    return fl


def peaks_for(device_kind: str) -> dict:
    """The chip's published peaks; a kind that the table lacks is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise LookupError(
            f"benchmarks/peaks.json has no row for device_kind "
            f"{device_kind!r}: add one, with its source")
    return table[device_kind]
