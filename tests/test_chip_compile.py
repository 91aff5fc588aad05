"""Compiles for a *described* TPU v5e: the only file that describes the chip.

The TPU's compiler is installed here and compiles for a chip that is not
attached (the `on-chip-measurement` guide §2.3). Nothing runs, so these
cases say nothing about results or times; they say that the chip's compiler
takes, at ViT-B/1024 shapes, every Pallas kernel the ``auto`` path can still
select, and that the fused predict program fits one chip's 16 GB. A compile
that passes here is not a chip run.

The topology is described inside a module-scoped fixture — never at import,
never ``autouse``, never from ``conftest.py`` — because only one process may
load the TPU's library: every xdist worker imports this file, and only the
worker that runs it may make the call. Code that asks
``jax.default_backend()`` still sees the CPU here, so the test steers those
branches itself (``monkeypatch``), not through an option of the program.
"""

import inspect
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tmr_tpu.ops.flash_attn import (
    flash_decomposed_attention,
    flash_windowed_attention,
)
from tmr_tpu.ops.pallas_attn import (
    packed_global_attention,
    packed_windowed_attention,
    pallas_decomposed_attention,
)

V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A sharding on the described chip, with the persistent compile cache
    off around the module: such a compile is written to the cache but
    cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """The program's ``jax.default_backend()`` branches take their TPU side
    (no interpret mode, TPU defaults), and the gates the ``auto`` path asks
    answer yes — their self-checks execute, which only a chip can."""
    from tmr_tpu.diagnostics import mosaic_gate
    from tmr_tpu.ops import causal_attn, flash_attn, kda, moe, pallas_attn
    from tmr_tpu.ops import pallas_nms

    def admits(name):
        def gate(*a, **k):
            return True

        gate.__name__ = name
        # still a Mosaic gate: inside a partitioned trace it answers no
        return mosaic_gate(gate)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for mod, name in ((flash_attn, "flash_attention_ok"),
                      (flash_attn, "flash_window_ok"),
                      (pallas_attn, "packed_window_ok"),
                      (pallas_attn, "packed_global_ok"),
                      (kda, "kda_chunk_ok"),
                      (causal_attn, "latent_kernel_ok"),
                      (moe, "pairs_kernels_ok"),
                      (pallas_nms, "pallas_nms_compiled_ok")):
        monkeypatch.setattr(mod, name, admits(name))


# ViT-B at 1024: 64x64 token grid, 12 heads of 64; the 14x14 windows of a
# 70x70-padded grid are 25 per image
_G, _H, _D, _WIN, _NWIN = 64, 12, 64, 14, 25
_SCALE = _D**-0.5


def _attn_case(fn, grid, batch, grad=False):
    """One attention formulation at its production shape; ``grad`` compiles
    what the train step differentiates, as the gates' grad half does."""
    def forward(q, k, v, rh, rw):
        return fn(q, k, v, rh, rw, (grid, grid), _SCALE)

    def loss(*args):
        return jnp.sum(forward(*args).astype(jnp.float32) ** 2)

    def case(sds):
        q = sds((batch, _H, grid * grid, _D), jnp.bfloat16)
        rel = sds((grid, grid, _D), jnp.float32)
        run = jax.grad(loss, argnums=(0, 1, 2)) if grad else forward
        return run, (q, q, q, rel, rel)

    return case


def _packed_case(windows, heads, head_dim, grad=False):
    """The windowed blocks' packed path on the ``qkv`` product's own
    output, 14 x 16 rows a window, at a cell's production shape; ``grad``
    compiles what the train step differentiates."""
    def forward(qkv, rh, rw):
        return packed_windowed_attention(qkv, rh, rw, (_WIN, _WIN), heads,
                                         head_dim**-0.5)

    def loss(*args):
        return jnp.sum(forward(*args).astype(jnp.float32) ** 2)

    def case(sds):
        qkv = sds((windows * _WIN * 16, 3 * heads * head_dim), jnp.bfloat16)
        rel = sds((_WIN, _WIN, head_dim), jnp.float32)
        return (jax.grad(loss, argnums=(0, 1, 2)) if grad else forward), (
            qkv, rel, rel)

    return case


def _packed_global_case(images, heads, head_dim, grad=False):
    """The global blocks' packed path on the ``qkv`` product's own output,
    4,096 rows an image, at a cell's production shape; ``grad`` compiles
    what the train step differentiates."""
    def forward(qkv, rh, rw):
        return packed_global_attention(qkv, rh, rw, (_G, _G), heads,
                                       head_dim**-0.5)

    def loss(*args):
        return jnp.sum(forward(*args).astype(jnp.float32) ** 2)

    def case(sds):
        qkv = sds((images * _G * _G, 3 * heads * head_dim), jnp.bfloat16)
        rel = sds((_G, _G, head_dim), jnp.float32)
        return (jax.grad(loss, argnums=(0, 1, 2)) if grad else forward), (
            qkv, rel, rel)

    return case


# kimilinear_fscd147.eval: 4 images of 4,096 tokens, 32 heads of 128
_KDA = dict(batch=4, seq=4096, heads=32, head_dim=128, hidden=2304)


def _case_kda_chunk(sds):
    """The chunked delta rule's kernel on what ``KDAMixer`` holds: q, k, g
    float32 and v bfloat16 as (B, S, H, d), beta (B, S, H)."""
    from tmr_tpu.ops.kda import kda_chunk_kernel

    shape = (_KDA["batch"], _KDA["seq"], _KDA["heads"], _KDA["head_dim"])
    f32 = sds(shape, jnp.float32)
    return (lambda *a: kda_chunk_kernel(*a, jnp.bfloat16)), (
        f32, f32, sds(shape, jnp.bfloat16), f32, sds(shape[:3], jnp.float32))


def _pairs_case(tokens, k, d):
    """The row kernels that bring the experts' results to their tokens
    (``ops/moe.py:sum_rows``) on what ``MoEFFN`` holds: the products'
    (tokens x k, d) bfloat16 result, the weights, who is held here and each
    pair's row."""
    from tmr_tpu.ops import moe

    def case(sds):
        return moe.sum_rows, (
            sds((tokens * k, d), jnp.bfloat16), sds((tokens, k), jnp.float32),
            sds((tokens, k), jnp.bool_), sds((tokens, k), jnp.int32))

    return case


def _latent_case(rope):
    """Latent attention's kernel on what ``MLAMixer`` holds in both trunk
    cells: 4 images of 4,096 tokens, 32 heads of 128 + 64 / 128, q, kv and
    k_pe as the projections write them; ``rope``: the rotation inside."""
    from tmr_tpu.ops.causal_attn import latent_attention_kernel
    from tmr_tpu.ops.rope import yarn_inv_freq

    rot = (tuple(yarn_inv_freq(64, 10000, 64, 4096, 32, 1).tolist()),
           1.0) if rope else None

    def case(sds):
        b, s, h = _KDA["batch"], _KDA["seq"], _KDA["heads"]
        return (lambda *a: latent_attention_kernel(*a, h, 192 ** -0.5, rot)), (
            sds((b, s, h * 192), jnp.bfloat16),
            sds((b, s, h * 256), jnp.bfloat16), sds((b, s, 64), jnp.bfloat16))

    return case


def _case_nms(sds):
    """The decode tail's shape: 2 images x max_detections slots, vmapped as
    postprocess.batched_nms does."""
    from tmr_tpu.ops.pallas_nms import nms_keep_mask_pallas

    fn = jax.vmap(lambda b, s, v: nms_keep_mask_pallas(b, s, 0.5, v))
    return fn, (sds((2, 2000, 4), jnp.float32), sds((2, 2000), jnp.float32),
                sds((2, 2000), jnp.bool_))


def _case_int8_matmul(sds):
    """A decoder-conv-as-matmul shape: the 128x128 grid's 16384 rows."""
    from tmr_tpu.ops.pallas_int8 import int8_matmul

    return int8_matmul, (sds((16384, 1024), jnp.int8),
                         sds((1024, 1024), jnp.int8),
                         sds((16384, 1), jnp.float32),
                         sds((1, 1024), jnp.float32))


def _case_predict_program(sds):
    """The fused predict program of chip_smoke.py and bench.py: ViT-B/1024,
    emb 512, 2x upsample, fusion, bf16, two images, parameters as shapes."""
    import numpy as np

    from tmr_tpu.config import preset
    from tmr_tpu.inference import Predictor

    cfg = preset("TMR_FSCD147", backbone="sam_vit_b", image_size=1024,
                 compute_dtype="bfloat16")
    pred = Predictor(cfg)
    image = jnp.zeros((2, 1024, 1024, 3), jnp.float32)
    ex = np.asarray([[[0.2, 0.2, 0.3, 0.3]]] * 2, np.float32)
    params = jax.eval_shape(
        pred.model.init, jax.random.key(0), image[:1], jnp.asarray(ex[:1])
    )["params"]
    params = jax.tree.map(lambda x: sds(x.shape, x.dtype), params)
    fn = inspect.unwrap(pred._get_fn(pred.pick_capacity(ex, 1024)),
                        stop=lambda f: hasattr(f, "lower"))
    return fn, (params, None, sds(image.shape, image.dtype),
                sds(ex.shape, ex.dtype))


CASES = {
    "pallas_global": _attn_case(pallas_decomposed_attention, _G, 1),
    "flash_global": _attn_case(flash_decomposed_attention, _G, 1),
    "flash_global_grad": _attn_case(flash_decomposed_attention, _G, 1,
                                    grad=True),
    "flash_window": _attn_case(flash_windowed_attention, _WIN, _NWIN),
    "flash_window_grad": _attn_case(flash_windowed_attention, _WIN, _NWIN,
                                    grad=True),
    # vitb_fscd147.eval: 16 images of 25 windows, 12 heads of 64;
    # vith_rpine.eval: 8 images, 16 heads of 80
    "packed_window_vitb": _packed_case(16 * _NWIN, 12, 64),
    "packed_window_vith": _packed_case(8 * _NWIN, 16, 80),
    "packed_window_grad": _packed_case(2 * _NWIN, 12, 64, grad=True),
    "packed_global_vitb": _packed_global_case(16, 12, 64),
    "packed_global_vith": _packed_global_case(8, 16, 80),
    "packed_global_grad": _packed_global_case(1, 12, 64, grad=True),
    "kda_chunk_kimi": _case_kda_chunk,
    "latent_kernel_kimi": _latent_case(rope=False),
    "latent_kernel_xing": _latent_case(rope=True),
    # a cell's tokens a batch, choices a token and hidden width; and one
    # image of the 1536 bucket, which no cell runs
    "pairs_kimi": _pairs_case(4 * 4096, 8, 2304),
    "pairs_xing": _pairs_case(4 * 4096, 4, 3584),
    "pairs_granite": _pairs_case(2 * 4096, 10, 4096),
    "pairs_granite_1536": _pairs_case(9216, 10, 4096),
    "nms": _case_nms,
    "int8_matmul": _case_int8_matmul,
    "predict_program": _case_predict_program,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, one_chip, as_tpu):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = CASES[case](sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), (
        f"{case}: the compiled program holds no Mosaic kernel"
    )
    if case == "predict_program":
        mem = compiled.memory_analysis()
        need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        assert need < V5E_HBM_BYTES, f"{need} bytes do not fit 16 GB"


def test_windowed_block_moves_its_operands_once(one_chip, as_tpu):
    """One windowed ViT-B block at the cell's batch, compiled for the v5e:
    between the ``qkv`` product and ``proj`` the program holds the kernel
    and no ``concatenate``, and no ``pad`` whose result is larger than q on
    the rows the kernel reads (the one pad there is those rows' own, a
    window row of x from 14 to 16 tokens, ahead of ``qkv``); no ``copy`` or
    ``transpose`` under the scope has a q-sized result either."""
    import re

    from tmr_tpu.models.vit import Block

    blk = Block(num_heads=_H, window_size=_WIN, rel_pos_size=(_G, _G),
                dtype=jnp.bfloat16, name="blocks_0")
    x = jax.ShapeDtypeStruct((16, _G, _G, _H * _D), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(blk.init, jax.random.key(0), x))
    text = jax.jit(blk.apply).lower(params, x).compile().as_text()
    q_elems = 16 * _NWIN * _WIN * 16 * _H * _D
    seen = set()
    # the entry computation's instructions are what the device runs
    for line in text[text.index("\nENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(.*op_name=\"[^\"]*blocks_0/attn/", line)
        if not m:
            continue
        elems = 1
        for d in filter(None, m.group(1).split(",")):
            elems *= int(d)
        seen.add(m.group(2))
        assert m.group(2) != "concatenate" or elems < q_elems, line
        assert m.group(2) != "pad" or elems <= q_elems, line
        assert m.group(2) not in ("copy", "transpose") or elems < q_elems, \
            line
    assert "custom-call" in seen and "fusion" in seen, seen


def test_global_block_moves_its_operands_once(one_chip, as_tpu):
    """One global ViT-B block at the cell's batch, compiled for the v5e:
    between the ``qkv`` product and ``proj`` the program holds the kernel
    and nothing that moves an operand: no ``concatenate``, ``pad``,
    ``transpose`` or ``copy`` under the scope has a result as large as q,
    and no stock ``flash_attention`` kernel is left."""
    import re

    from tmr_tpu.models.vit import Block

    blk = Block(num_heads=_H, window_size=0, rel_pos_size=(_G, _G),
                dtype=jnp.bfloat16, name="blocks_2")
    x = jax.ShapeDtypeStruct((16, _G, _G, _H * _D), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(blk.init, jax.random.key(0), x))
    lowered = jax.jit(blk.apply).lower(params, x)
    assert "flash_attention" not in lowered.as_text()
    text = lowered.compile().as_text()
    q_elems = 16 * _G * _G * _H * _D
    seen = set()
    for line in text[text.index("\nENTRY"):].splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(.*op_name=\"[^\"]*blocks_2/attn/", line)
        if not m:
            continue
        elems = 1
        for d in filter(None, m.group(1).split(",")):
            elems *= int(d)
        seen.add(m.group(2))
        assert m.group(2) not in (
            "concatenate", "pad", "copy", "transpose") or elems < q_elems, line
    assert "custom-call" in seen and "fusion" in seen, seen


def _kda_layer(sharding, batch=_KDA["batch"], seq=_KDA["seq"]):
    """A KDA mixer at the cell's widths, named as a trunk layer names it,
    its parameters and input as shapes under ``sharding``."""
    from tmr_tpu.models.lm_trunk import KDAMixer

    mixer = KDAMixer(_KDA["heads"], _KDA["head_dim"], dtype=jnp.bfloat16,
                     name="attn")
    x = jax.ShapeDtypeStruct((batch, seq, _KDA["hidden"]), jnp.bfloat16,
                             sharding=sharding)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        jax.eval_shape(mixer.init, jax.random.key(0), x))
    return mixer, params, x


def test_kda_layer_keeps_a_chunk_on_the_chip(one_chip, as_tpu):
    """One KDA layer at the cell's batch, compiled for the v5e: the
    recurrence is one Mosaic kernel, and nothing under ``attn/scan/`` moves
    its operands (no ``transpose``, ``concatenate`` or ``pad``, in the entry
    computation or inside a fusion): q, k, v, g are read where the
    projections left them."""
    import re

    mixer, params, x = _kda_layer(one_chip)
    text = jax.jit(mixer.apply).lower(params, x).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    under_scan = [
        m.group(1) for m in re.finditer(
            r"= \S+ ([\w\-]+)\([^\n]*op_name=\"[^\"]*attn/scan/", text)]
    assert "custom-call" in under_scan, under_scan
    assert not {"transpose", "concatenate", "pad"} & set(under_scan), \
        sorted(set(under_scan))


def test_expert_layer_brings_its_results_home_by_two_kernels(one_chip,
                                                              as_tpu):
    """One expert layer at ``granite4h_fscd147.eval``'s widths and batch,
    compiled for the v5e: under ``dispatch/`` the program holds the two row
    kernels and XLA's one gather of the rows in, and nothing there is as
    large as the pairs in float32 (the (tokens, k, D) relayout that the XLA
    form of ``combine`` costs on a TPU, 2 GB a layer and batch)."""
    import re

    from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS, MoEFFN

    z = TRUNK_CONFIGS["granite4_h_small_share2"]
    tokens, d, k = 2 * 4096, z["hidden"], z["top_k"]
    layer = MoEFFN(z["num_experts"], z["experts_held"], 0, k,
                   z.get("routed_scale", 1.0), z["expert_width"],
                   z.get("router", "sigmoid_bias"), z.get("shared_width"),
                   jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2, 4096, d), jnp.bfloat16, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(layer.init, jax.random.key(0), x))
    text = jax.jit(lambda p, x: layer.apply(p, x, mutable=["trunk_stats"])[
        0]).lower(params, x).compile().as_text()
    under = re.findall(
        r"= (\w+)\[([\d,]*)\]\S* ([\w\-]+)\([^\n]*op_name=\"[^\"]*"
        r"MoEFFN/dispatch/", text)
    kernels = [shape for _, shape, op in under if op == "custom-call"
               and shape in (f"{tokens * k + 512},1,{d // 2}",
                             f"{tokens},{d}")]
    assert len(kernels) == 2, kernels
    pairs_f32 = tokens * k * d * 4
    import math

    sizes = {(dtype, shape): math.prod(int(n) for n in shape.split(","))
             * {"f32": 4, "u32": 4, "s32": 4, "bf16": 2}.get(dtype, 1)
             for dtype, shape, _ in under if shape}
    assert max(sizes.values()) < pairs_f32, max(sizes, key=sizes.get)


@pytest.mark.parametrize("family", ["kimi_linear_a3b_share2",
                                    "xing4_a4b_stage6"])
def test_mla_layer_keeps_its_scores_on_the_chip(family, one_chip, as_tpu):
    """One latent-attention layer at a trunk cell's widths and batch,
    compiled for the v5e: scores, softmax and values are one Mosaic kernel
    under ``attn/softmax/``, and nothing of the layer moves an operand the
    size of q (no ``transpose``, ``copy``, ``concatenate`` or ``pad``, in
    the entry computation or inside a fusion): q, kv and k_pe are read where
    the projections left them, and the kernel's output where ``o_proj``
    reads it."""
    import re

    from tmr_tpu.models.lm_trunk import TRUNK_CONFIGS, MLAMixer

    z = TRUNK_CONFIGS[family]
    mixer = MLAMixer(z["num_heads"], z["qk_nope_dim"], z["qk_pe_dim"],
                     z["v_dim"], z["kv_rank"], z.get("q_rank"), z.get("rope"),
                     dtype=jnp.bfloat16, name="attn")
    b, s = _KDA["batch"], _KDA["seq"]
    x = jax.ShapeDtypeStruct((b, s, z["hidden"]), jnp.bfloat16,
                             sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(mixer.init, jax.random.key(0), x))
    text = jax.jit(mixer.apply).lower(params, x).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    under_softmax = [m.group(1) for m in re.finditer(
        r"= \S+ ([\w\-]+)\([^\n]*op_name=\"[^\"]*attn/softmax/", text)]
    assert "custom-call" in under_softmax, under_softmax
    q_elems = b * s * z["num_heads"] * z["v_dim"]  # the smallest of q, kv, o
    for m in re.finditer(r"= \w+\[([\d,]*)\]\S* ([\w\-]+)\([^\n]*"
                         r"op_name=\"[^\"]*attn/", text):
        elems = 1
        for d in filter(None, m.group(1).split(",")):
            elems *= int(d)
        assert m.group(2) not in ("transpose", "copy", "concatenate",
                                  "pad") or elems < q_elems, m.group(0)[:300]


def _shallow_vit_b(cfg):
    """The configuration's model with ViT-B's widths and both of its block
    kinds (one windowed, one global) at depth 2: what a partitioned
    program lowers does not depend on how often the pair repeats, and the
    compile stays short."""
    from tmr_tpu.models import build_model

    model = build_model(cfg)
    return model.clone(template_capacity=9, backbone=model.backbone.clone(
        depth=2, global_attn_indexes=(1,)))


def _mesh_case_train_step(devices):
    """The trainer's own jit (``Trainer._jit_step_under_mesh``) over a
    two-chip data mesh: ``main.py --mesh_data -1 --multi_gpu`` on a host
    with more than one chip."""
    import types

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from tmr_tpu.config import preset
    from tmr_tpu.parallel import make_mesh
    from tmr_tpu.parallel.sharding import state_sharding
    from tmr_tpu.train.loop import Trainer
    from tmr_tpu.train.state import create_train_state, make_train_step

    cfg = preset("TMR_FSCD147", backbone="sam_vit_b", image_size=1024,
                 compute_dtype="bfloat16")
    model = _shallow_vit_b(cfg)
    mesh = make_mesh((2, 1), devices=devices)
    image = jax.ShapeDtypeStruct((2, 1024, 1024, 3), jnp.float32)
    boxes = jax.ShapeDtypeStruct((2, 1, 4), jnp.float32)
    state = jax.eval_shape(
        lambda im, ex: create_train_state(model, cfg, jax.random.key(0),
                                          im[:1], ex[:1]), image, boxes)
    sharding = state_sharding(state, mesh)
    step = Trainer._jit_step_under_mesh(
        types.SimpleNamespace(mesh=mesh), make_train_step(model, cfg),
        sharding)

    def place(tree, shardings):
        return jax.tree.map(
            lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=sh), tree, shardings)

    data = NamedSharding(mesh, P("data"))
    batch = {"image": image, "exemplars": boxes, "gt_boxes": boxes,
             "gt_valid": jax.ShapeDtypeStruct((2, 1), jnp.bool_)}
    return mesh, step, (place(state, sharding),
                        place(batch, jax.tree.map(lambda _: data, batch)))


def _mesh_case_serve_tp2(devices):
    """A tensor-parallel serving target (``Predictor._get_sharded_fn``
    through ``compile_sharded``) on one two-chip replica group."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from tmr_tpu.config import preset
    from tmr_tpu.inference import Predictor
    from tmr_tpu.serve.meshplan import MeshPlan

    cfg = preset("TMR_FSCD147", backbone="sam_vit_b", image_size=1024,
                 compute_dtype="bfloat16")
    pred = Predictor(cfg, model=_shallow_vit_b(cfg))
    image = jax.ShapeDtypeStruct((1, 1024, 1024, 3), jnp.float32)
    boxes = jax.ShapeDtypeStruct((1, 1, 4), jnp.float32)
    pred.params = jax.eval_shape(pred.model.init, jax.random.key(0), image,
                                 boxes)["params"]
    target = MeshPlan("tp2", devices=devices).group_targets[0]
    pshard, repl = pred._sharded_shardings(target)
    params = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        pred.params, pshard)
    repl = NamedSharding(target.mesh, P())
    return target.mesh, pred._get_sharded_fn(9, target), (
        params, None,
        jax.ShapeDtypeStruct(image.shape, image.dtype, sharding=repl),
        jax.ShapeDtypeStruct(boxes.shape, boxes.dtype, sharding=repl))


def _mesh_case_kda_layer(devices):
    """A KDA layer of the trunk alone (``MoEFFN`` refuses a partitioned
    trace, so no whole trunk can be traced here), its input split along the
    hidden width over two chips: the projections' contraction is
    partitioned."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from tmr_tpu.parallel.compat import partitioned

    mesh = Mesh(np.asarray(devices).reshape(1, 2), ("data", "model"))
    mixer, params, x = _kda_layer(NamedSharding(mesh, P()), batch=1,
                                  seq=1024)
    x = jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=NamedSharding(mesh, P(None, None, "model")))
    return mesh, jax.jit(partitioned(mixer.apply, mesh)), (params, x)


_VIT_GATES = ("flash_attention_ok", "packed_window_ok", "packed_global_ok")
MESH_CASES = {"train_step_dp2": (_mesh_case_train_step, _VIT_GATES),
              "serve_tp2": (_mesh_case_serve_tp2, _VIT_GATES),
              "kda_layer_tp2": (_mesh_case_kda_layer, ("kda_chunk_ok",))}


@pytest.mark.parametrize("case", sorted(MESH_CASES))
def test_partitioned_program_compiles_for_two_v5e_chips(
    case, topo, one_chip, as_tpu
):
    """A program XLA partitions over more than one chip cannot hold a
    Mosaic kernel ("Mosaic kernels cannot be automatically partitioned"),
    and on a TPU the gates admit the kernels: such a program must be
    traced with them off (``parallel.compat.partitioned``), record that
    with cause ``partitioned``, and compile for the two chips."""
    from tmr_tpu.diagnostics import drain_gate_refusals

    drain_gate_refusals()
    build, gates = MESH_CASES[case]
    mesh, fn, args = build(topo.devices[:2])
    jitted = inspect.unwrap(fn, stop=lambda f: hasattr(f, "lower"))
    with jax.sharding.set_mesh(mesh):
        compiled = jitted.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    assert "all-reduce" in text, "nothing was partitioned over the chips"
    causes = {(r["gate"], r["cause"]) for r in drain_gate_refusals()}
    for gate in gates:
        assert (gate, "partitioned") in causes, (gate, causes)


@pytest.mark.parametrize("gate,args", [
    ("pallas_fused_ok", (64, 64, 64, 512, 512)),
    ("pallas_xcorr_ok", (512, 128, 128, 15)),
])
def test_retired_kernels_answer_no_with_the_compilers_words(
    gate, args, as_tpu
):
    """The two kernels the chip's compiler refuses are not selectable on a
    TPU: their gates answer no with a structured cause that carries the
    compiler's message, never ``exception``."""
    from tmr_tpu.diagnostics import drain_gate_refusals
    from tmr_tpu.ops import pallas_attn, pallas_xcorr

    drain_gate_refusals()
    fn = {"pallas_fused_ok": pallas_attn.pallas_fused_ok,
          "pallas_xcorr_ok": pallas_xcorr.pallas_xcorr_ok}[gate]
    getattr(fn, "cache_clear", lambda: None)()
    assert fn(*args) is False
    getattr(fn, "cache_clear", lambda: None)()
    (rec,) = drain_gate_refusals()
    assert rec["gate"] == gate and rec["cause"] == "unsupported-shape"
    assert "Mosaic" in rec["message"]
