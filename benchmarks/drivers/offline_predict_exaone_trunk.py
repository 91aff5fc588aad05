"""Offline evaluation through ``Predictor.__call__`` with the K-EXAONE trunk
as the backbone (``tmr_tpu/models/lm_trunk.py``,
``k_exaone_236b_a23b_share8``).

Everything is a sibling's, by import, and nothing is written out again: the
window, the compiled texts and the release are ``offline_predict``'s; the
set-up (the weights' draw a layer at a time, the warm-up), the served batch
with its routing table and the balancing of the routers' selection bias
are ``offline_predict_lm_trunk``'s; the gate report is
``offline_predict_xing_trunk``'s and the check is
``offline_predict_granite_trunk.check_against``, which takes its reference
as an argument. The functions that name their reference and their sizes as
module globals are taken as they are and bound to this cell's
(``offline_predict_lfm2_trunk._bound``): the reference is
``reference_exaone_trunk``, the work ``work_exaone_trunk``, the sizes this
file's ``_trunk_sizes``. The router is the sigmoid one with a selection
bias and a shared expert, as ``kimilinear_fscd147``'s, so its balance is
that cell's rule, by this reference's scores of each expert layer's own
input (the stream: no norm stands ahead of a sub-layer here).
"""

from __future__ import annotations

import functools

from benchmarks import reference_exaone_trunk, work_exaone_trunk
from benchmarks.drivers import (offline_predict_granite_trunk,
                                offline_predict_lm_trunk)
from benchmarks.drivers.offline_predict import (_template_cells, hlo_texts,
                                                release, window)
from benchmarks.drivers.offline_predict_lfm2_trunk import _bound
from benchmarks.drivers.offline_predict_xing_trunk import _say_gates

__all__ = ["setup", "window", "hlo_texts", "work_per_image", "release",
           "check"]


def _trunk_sizes(model: dict) -> dict:
    """The configuration file's sizes under the program's own names
    (``lm_trunk.TRUNK_CONFIGS``)."""
    return dict(
        hidden=model["hidden_size"],
        layers=tuple(tuple(layer) for layer in model["layers"]),
        num_heads=model["num_heads"], kv_heads=model["num_key_value_heads"],
        head_dim=model["head_dim"],
        attn_scale=float(model["head_dim"]) ** -0.5, qk_norm=True,
        sliding_window=model["sliding_window"],
        sliding_rope_theta=float(model["rope_theta"]), post_norms=True,
        dense_width=model["intermediate_size"],
        expert_width=model["moe_intermediate_size"],
        num_experts=model["router_experts"],
        experts_held=model["experts_held"],
        expert_offset=model["expert_offset"],
        top_k=model["num_experts_per_token"],
        routed_scale=float(model["routed_scaling_factor"]))


_agree_on_sizes = _bound(offline_predict_granite_trunk._agree_on_sizes,
                         _trunk_sizes=_trunk_sizes)
balance_routers = _bound(offline_predict_lm_trunk.balance_routers,
                         reference_lm_trunk=reference_exaone_trunk)
setup = _bound(offline_predict_lm_trunk.setup,
               _agree_on_sizes=_agree_on_sizes, _say_gates=_say_gates,
               balance_routers=balance_routers)


def work_per_image(ctx, state: dict) -> dict:
    """The experts' products at the pairs the program's own run-time
    counters counted (every call of this process), the sliding windows'
    and the full layer's scores and values, the forward as a whole at the
    expected pairs."""
    from tmr_tpu.obs import get_registry

    model = ctx.config["model"]
    cells = _template_cells(state["exemplars"], state["feature_hw"])
    batch = int(ctx.workload["traffic"]["batch"])
    counted = get_registry().counters("trunk.moe.")
    tokens = (state["size"] // model["patch_size"]) ** 2
    pairs = tokens * counted["pairs_here"] / counted["tokens"]
    even = (tokens * model["num_experts_per_token"] * model["experts_held"]
            / model["router_experts"])
    ctx.say(f"pairs an image brings to a layer's held experts: counted "
            f"{pairs:.1f}, an even router {even:.1f}")
    size = state["size"]
    return {
        "forward_flops": work_exaone_trunk.forward_flops_per_image(
            model, size, cells),
        "swa_attn": work_exaone_trunk.swa_attn_per_image(model, size),
        "gqa_attn": work_exaone_trunk.gqa_attn_per_image(model, size),
        "moe_experts": work_exaone_trunk.moe_experts_per_image(
            model, size, batch, pairs),
    }


check = functools.partial(offline_predict_granite_trunk.check_against,
                          reference_exaone_trunk)
