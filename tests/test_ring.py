"""Sequence/context parallelism (tmr_tpu/parallel/ring.py): ring attention,
Ulysses all-to-all, and the ViT decomposed-rel-pos ring variant, validated
against dense attention on the 8-device CPU mesh."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from tmr_tpu.parallel.compat import shard_map

from tmr_tpu.parallel.ring import (
    dense_attention,
    make_ring_attention_fn,
    ring_attention,
    ring_decomposed_attention,
    ulysses_attention,
)

B, H, S, D = 2, 4, 64, 16
SEQ_SPEC = P(None, None, "seq", None)


def seq_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("seq",))


def rand_qkv(seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("n", [4, 8])
def test_ring_matches_dense(n):
    q, k, v = rand_qkv(0)
    mesh = seq_mesh(n)
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq"),
        mesh=mesh, in_specs=(SEQ_SPEC,) * 3, out_specs=SEQ_SPEC,
        check_vma=False,
    )
    got = jax.jit(fn)(q, k, v)
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_with_bias_matches_dense():
    n = 4
    q, k, v = rand_qkv(1)
    rng = np.random.default_rng(2)
    bias = jnp.asarray(rng.standard_normal((1, H, S, S)), jnp.float32)
    blk = S // n

    mesh = seq_mesh(n)

    def local(q, k, v):
        def bias_fn(qi, ki):
            return jax.lax.dynamic_slice(
                bias, (0, 0, qi * blk, ki * blk), (1, H, blk, blk)
            )

        return ring_attention(q, k, v, "seq", bias_fn=bias_fn)

    got = jax.jit(shard_map(local, mesh=mesh, in_specs=(SEQ_SPEC,) * 3,
                            out_specs=SEQ_SPEC, check_vma=False))(q, k, v)
    want = dense_attention(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n", [2, 4])
def test_ulysses_matches_dense(n):
    q, k, v = rand_qkv(3)
    mesh = seq_mesh(n)
    fn = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "seq"),
        mesh=mesh, in_specs=(SEQ_SPEC,) * 3, out_specs=SEQ_SPEC,
        check_vma=False,
    )
    got = jax.jit(fn)(q, k, v)
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_bf16_inputs():
    q, k, v = rand_qkv(4, jnp.bfloat16)
    mesh = seq_mesh(4)
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq"),
        mesh=mesh, in_specs=(SEQ_SPEC,) * 3, out_specs=SEQ_SPEC,
        check_vma=False,
    )
    got = jax.jit(fn)(q, k, v)
    assert got.dtype == jnp.bfloat16
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


@pytest.mark.slow
def test_ring_gradients_match_dense():
    q, k, v = rand_qkv(5)
    mesh = seq_mesh(4)
    ring = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq"),
        mesh=mesh, in_specs=(SEQ_SPEC,) * 3, out_specs=SEQ_SPEC,
        check_vma=False,
    )

    def loss_ring(q, k, v):
        return (ring(q, k, v) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v) ** 2).sum()

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_dense = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                   rtol=5e-4, atol=5e-4)


def test_ring_decomposed_matches_vit_dense():
    """Row-sharded ring attention with decomposed rel-pos == the dense
    decomposed attention of models/vit.py Attention (sam_ViT.py:325-361)."""
    n = 4
    GH, GW = 8, 8  # token grid; S = 64
    hd = D
    rng = np.random.default_rng(6)
    q, k, v = rand_qkv(7)
    rh = jnp.asarray(rng.standard_normal((GH, GH, hd)), jnp.float32)
    rw = jnp.asarray(rng.standard_normal((GW, GW, hd)), jnp.float32)

    # dense oracle, exactly the vit.py:127-132 formulation
    scale = hd ** -0.5
    r_q = np.asarray(q).reshape(B, H, GH, GW, hd)
    rel_h = np.einsum("bnhwc,hkc->bnhwk", r_q, np.asarray(rh))
    rel_w = np.einsum("bnhwc,wkc->bnhwk", r_q, np.asarray(rw))
    bias = rel_h[..., :, None] + rel_w[..., None, :]
    bias = jnp.asarray(bias.reshape(B, H, S, S))
    want = dense_attention(q, k, v, bias=bias, scale=scale)

    mesh = seq_mesh(n)
    fn = shard_map(
        lambda q, k, v: ring_decomposed_attention(q, k, v, rh, rw, GW, "seq"),
        mesh=mesh, in_specs=(SEQ_SPEC,) * 3, out_specs=SEQ_SPEC,
        check_vma=False,
    )
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_vit_seq_parallel_matches_dense():
    """SamViT with a 'seq' mesh (ring-attention global blocks) must produce
    the same features as the single-device dense path."""
    from tmr_tpu.models.vit import SamViT

    tiny = dict(embed_dim=32, depth=2, num_heads=2, global_attn_indexes=(1,),
                window_size=2, out_chans=8, pretrain_img_size=64)
    x = jnp.asarray(
        np.random.default_rng(9).standard_normal((2, 64, 64, 3)), jnp.float32
    )
    dense_model = SamViT(**tiny)
    params = dense_model.init(jax.random.key(0), x)["params"]
    want = dense_model.apply({"params": params}, x)

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "seq"))
    ring_model = SamViT(**tiny, seq_mesh=mesh)
    got = jax.jit(
        lambda p, v: ring_model.apply({"params": p}, v)
    )(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_vit_seq_parallel_grad_matches_dense():
    """Backward pass through the ring island matches the dense grad (the
    training path under context parallelism)."""
    from tmr_tpu.models.vit import SamViT

    tiny = dict(embed_dim=16, depth=1, num_heads=2, global_attn_indexes=(0,),
                window_size=0, out_chans=8, pretrain_img_size=32)
    x = jnp.asarray(
        np.random.default_rng(10).standard_normal((2, 32, 32, 3)), jnp.float32
    )
    dense_model = SamViT(**tiny)
    params = dense_model.init(jax.random.key(1), x)["params"]

    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    ring_model = SamViT(**tiny, seq_mesh=mesh)

    def loss(model, p):
        return (model.apply({"params": p}, x) ** 2).mean()

    g_dense = jax.jit(jax.grad(partial(loss, dense_model)))(params)
    g_ring = jax.jit(jax.grad(partial(loss, ring_model)))(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4
        ),
        g_dense, g_ring,
    )


def test_vit_seq_parallel_batch1_on_dp_mesh():
    """Eval batch (1) not divisible by the data axis must fall back to a
    replicated batch instead of crashing (regression)."""
    from tmr_tpu.models.vit import SamViT

    tiny = dict(embed_dim=32, depth=1, num_heads=2, global_attn_indexes=(0,),
                window_size=0, out_chans=8, pretrain_img_size=64)
    x = jnp.asarray(
        np.random.default_rng(11).standard_normal((1, 64, 64, 3)), jnp.float32
    )
    dense = SamViT(**tiny)
    params = dense.init(jax.random.key(2), x)["params"]
    want = dense.apply({"params": params}, x)

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "seq"))
    ring = SamViT(**tiny, seq_mesh=mesh)
    got = jax.jit(lambda p, v: ring.apply({"params": p}, v))(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_vit_seq_parallel_composes_with_tp_mesh():
    """Heads shard over 'model' inside the ring island (TP+SP compose)."""
    from tmr_tpu.models.vit import SamViT

    tiny = dict(embed_dim=32, depth=1, num_heads=2, global_attn_indexes=(0,),
                window_size=0, out_chans=8, pretrain_img_size=64)
    x = jnp.asarray(
        np.random.default_rng(12).standard_normal((2, 64, 64, 3)), jnp.float32
    )
    dense = SamViT(**tiny)
    params = dense.init(jax.random.key(3), x)["params"]
    want = dense.apply({"params": params}, x)

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                ("data", "model", "seq"))
    ring = SamViT(**tiny, seq_mesh=mesh)
    got = jax.jit(lambda p, v: ring.apply({"params": p}, v))(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_make_mesh_axis_name_validation():
    from tmr_tpu.parallel.mesh import make_mesh

    with pytest.raises(ValueError):
        make_mesh((2, 2), axis_names=("data",))
    m = make_mesh((2, 2, 2))
    assert m.axis_names == ("data", "model", "seq")
    m2 = make_mesh((4,), axis_names=("replica",))
    assert m2.axis_names == ("replica",)


def test_make_ring_attention_fn_convenience():
    q, k, v = rand_qkv(8)
    mesh = seq_mesh(8)
    fn = make_ring_attention_fn(mesh)
    got = jax.jit(fn)(q, k, v)
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_blockwise_attention_matches_dense_at_global_grid():
    """The blockwise path is the production kernel for every global-attention
    block at real image sizes (h*w >= 1024 in models/vit.py); pin it to the
    dense oracle at a grid that actually takes that branch (32x32 = 1024
    tokens), with and without the decomposed rel-pos bias."""
    import numpy as np

    from tmr_tpu.models.vit import blockwise_decomposed_attention
    from tmr_tpu.parallel.ring import dense_attention

    rng = np.random.default_rng(5)
    B, H, gh, gw, D = 1, 2, 32, 32, 8
    S = gh * gw
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    rh = jnp.asarray(rng.standard_normal((gh, gh, D)), jnp.float32) * 0.2
    rw = jnp.asarray(rng.standard_normal((gw, gw, D)), jnp.float32) * 0.2
    scale = D**-0.5

    r_q = q.reshape(B, H, gh, gw, D)
    rel_h = jnp.einsum("bnhwc,hkc->bnhwk", r_q, rh)
    rel_w = jnp.einsum("bnhwc,wkc->bnhwk", r_q, rw)
    bias = (rel_h[..., :, None] + rel_w[..., None, :]).reshape(B, H, S, S)

    got = jax.jit(
        lambda *a: blockwise_decomposed_attention(*a, (gh, gw), scale)
    )(q, k, v, rh, rw)
    want = dense_attention(q, k, v, bias=bias, scale=scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    got_nb = jax.jit(
        lambda *a: blockwise_decomposed_attention(*a, None, None, (gh, gw), scale)
    )(q, k, v)
    want_nb = dense_attention(q, k, v, scale=scale)
    np.testing.assert_allclose(np.asarray(got_nb), np.asarray(want_nb),
                               rtol=1e-5, atol=1e-5)


def _packed_case(windows, heads, head_dim, dtype, seed=28):
    """qkv as ``nn.Dense(3 * dim)`` writes it on padded window rows, and
    the rel-pos tables, at the real 14 x 14 window."""
    from tmr_tpu.ops.pallas_attn import pad_window_rows

    rng = np.random.default_rng(seed)
    qkv = pad_window_rows(jnp.asarray(
        rng.standard_normal((windows, 14, 14, 3 * heads * head_dim)), dtype))
    rh = jnp.asarray(rng.standard_normal((14, 14, head_dim)) * 0.2,
                     jnp.float32)
    rw = jnp.asarray(rng.standard_normal((14, 14, head_dim)) * 0.2,
                     jnp.float32)
    return qkv, rh, rw


@pytest.mark.parametrize("windows,heads,head_dim,dtype", [
    (25, 12, 64, "float32"),     # one ViT-B image
    (25, 16, 80, "float32"),     # one ViT-H image: heads off the lane tile
    (7, 8, 80, "float32"),       # a count no group of windows divides
    (400, 12, 64, "bfloat16"),   # ViT-B at batch 16, the deployed dtype
])
def test_packed_windowed_attention_matches_blockwise(
    windows, heads, head_dim, dtype
):
    """``packed`` (ops/pallas_attn.packed_windowed_attention, the
    interpreter here) against the exact blockwise oracle on the unpacked
    heads, at window (14, 14). One window is one grid step, so every count
    of windows divides; float32 holds the oracle to its own rounding
    (the bias's three-part split is exact), bfloat16 to ``_self_check``'s
    tolerance, on the first, a middle and the last windows of the 400."""
    from tmr_tpu.ops.pallas_attn import (
        _packed_oracle,
        drop_window_pad,
        packed_windowed_attention,
    )

    qkv, rh, rw = _packed_case(windows, heads, head_dim, jnp.dtype(dtype))
    scale = head_dim**-0.5
    rows = 14 * 16  # a window's rows, pad tokens included
    got = jax.jit(lambda *a: packed_windowed_attention(
        *a, (14, 14), heads, scale))(qkv, rh, rw)
    assert got.shape == (windows * rows, heads * head_dim)
    pick = sorted({0, windows // 2, windows - 1}) if windows > 25 \
        else range(windows)
    take = np.concatenate([np.arange(w * rows, (w + 1) * rows) for w in pick])
    want = jax.jit(lambda *a: _packed_oracle(
        *a, (14, 14), heads, scale))(qkv[take], rh, rw)
    got, want = (np.asarray(drop_window_pad(x, (14, 14)), np.float32)
                 for x in (got[take], want))
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    else:
        assert np.abs(got - want).max() / np.abs(want).max() < 0.05


def test_packed_windowed_attention_grads_are_the_oracles():
    """The packed path's custom_vjp recomputes through the blockwise
    oracle: gradients w.r.t. qkv and both tables equal the oracle's own,
    and the pad tokens' rows get none."""
    from tmr_tpu.ops.pallas_attn import (
        _packed_oracle,
        drop_window_pad,
        packed_windowed_attention,
    )

    qkv, rh, rw = _packed_case(3, 4, 64, jnp.float32, seed=29)
    scale = 64**-0.5

    def loss(fn):
        return lambda *a: jnp.sum(drop_window_pad(
            fn(*a, (14, 14), 4, scale), (14, 14)) ** 2)

    g_got = jax.jit(jax.grad(loss(packed_windowed_attention),
                             argnums=(0, 1, 2)))(qkv, rh, rw)
    g_want = jax.jit(jax.grad(loss(_packed_oracle),
                              argnums=(0, 1, 2)))(qkv, rh, rw)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    pad_rows = np.asarray(g_got[0]).reshape(3, 14, 16, -1)[:, :, 14:]
    assert not pad_rows.any()


@pytest.mark.parametrize("backend,dtype,gate,rel_pos,env,taken", [
    ("cpu", "bfloat16", "admits", True, None, "dense"),
    ("tpu", "bfloat16", "admits", True, None, "packed"),
    ("tpu", "float32", "admits", True, None, "dense"),
    ("tpu", "bfloat16", "partitioned", True, None, "dense"),
    ("tpu", "bfloat16", "refuses", True, None, "dense"),
    ("tpu", "bfloat16", "admits", False, None, "dense"),
    ("tpu", "bfloat16", "admits", True, "folded", "packed"),
], ids=["cpu-dense", "tpu-packed", "tpu-float32-dense",
        "tpu-partitioned-dense", "tpu-gate-refuses-dense",
        "tpu-no-rel-pos-dense", "tpu-dead-variable-packed"])
def test_windowed_blocks_are_counted_by_formulation(
    backend, dtype, gate, rel_pos, env, taken, monkeypatch
):
    """``vit.win_attn.<formulation>`` counts the windowed blocks of a
    trace under what ``ops/pallas_attn.window_formulation`` answers: 8 for
    a depth-12 encoder with ViT-B's pattern of global blocks, 0 under the
    other name. ``packed`` only where the backend reads ``tpu``, the trace
    is bfloat16, the block has rel-pos tables and the gate admits the
    kernel (its self-check only a chip can run); the gate itself refuses
    with cause ``partitioned`` inside a program XLA partitions. No
    environment variable has a say: the old knob, set, changes nothing."""
    from tmr_tpu import diagnostics
    from tmr_tpu.models.vit import Attention, SamViT
    from tmr_tpu.obs import metrics
    from tmr_tpu.ops import pallas_attn
    from tmr_tpu.parallel.compat import partitioned

    if env is not None:
        monkeypatch.setenv("TMR_WIN_ATTN", env)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if gate != "partitioned":  # there the real gate answers, unasked
        monkeypatch.setattr(pallas_attn, "packed_window_ok",
                            lambda *a: gate == "admits")
    dtype = jnp.dtype(dtype)
    if rel_pos:
        blocks = 8
        model = SamViT(embed_dim=128, depth=12, num_heads=4,
                       global_attn_indexes=(2, 5, 8, 11), window_size=14,
                       out_chans=8, pretrain_img_size=448, dtype=dtype)
        x = jax.ShapeDtypeStruct((1, 448, 448, 3), jnp.float32)
    else:
        blocks = 1
        model = Attention(num_heads=4, use_rel_pos=False, windowed=True,
                          dtype=dtype)
        x = jax.ShapeDtypeStruct((4, 14, 14, 128), dtype)
    params = jax.eval_shape(model.init, jax.random.key(0), x)

    def trace():
        jax.eval_shape(model.apply, params, x)
        return pallas_attn.window_formulation(
            (14, 14), 4, 32, dtype, rel_pos)

    if gate == "partitioned":
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("model",))
        trace = partitioned(trace, mesh)
    diagnostics.drain_gate_refusals()
    metrics.get_registry().reset("vit.win_attn.")
    assert trace() == taken
    assert metrics.get_registry().counters("vit.win_attn.") == {
        taken: blocks}
    causes = {(r["gate"], r["cause"])
              for r in diagnostics.drain_gate_refusals()}
    assert causes == ({("packed_window_ok", "partitioned")}
                      if gate == "partitioned" else set())


def _packed_global_case(images, heads, head_dim, dtype, grid=32, seed=32):
    """qkv as ``nn.Dense(3 * dim)`` writes it on an image's rows of tokens,
    and rel-pos tables as ``get_rel_pos`` makes them (Toeplitz)."""
    from tmr_tpu.models.vit import get_rel_pos

    rng = np.random.default_rng(seed)
    qkv = jnp.asarray(rng.standard_normal(
        (images * grid * grid, 3 * heads * head_dim)), dtype)
    rh, rw = (get_rel_pos(grid, grid, jnp.asarray(
        rng.standard_normal((2 * grid - 1, head_dim)) * 0.2, jnp.float32))
        for _ in range(2))
    return qkv, rh, rw


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads,head_dim", [
    (2, 64),    # two heads share a 128-lane slab, told apart by a mask
    (8, 80),    # heads off the lane tile: all eight offsets of a group
])
def test_packed_global_attention_matches_blockwise(heads, head_dim, dtype):
    """``packed`` for the global blocks
    (ops/pallas_attn.packed_global_attention, the interpreter here) against
    the exact blockwise oracle on the unpacked heads, forward and gradient,
    on two images of a 32 x 32 grid (two query blocks an image; a smaller
    key block, so that there are four of eight grid rows each, is the next
    test's). float32 holds the oracle to its own rounding; bfloat16, where
    q * scale and q.RH are rounded to the operand dtype, to
    ``_self_check``'s tolerance. The gradient is the oracle's own
    (``custom_vjp``)."""
    from tmr_tpu.ops.pallas_attn import (
        _packed_global_oracle,
        packed_global_attention,
    )

    qkv, rh, rw = _packed_global_case(2, heads, head_dim, jnp.dtype(dtype))
    scale = head_dim**-0.5

    def loss(fn):
        def run(*a):
            out = fn(*a, (32, 32), heads, scale)
            return jnp.sum(out.astype(jnp.float32) ** 2), out
        return jax.jit(jax.value_and_grad(run, argnums=(0, 1, 2),
                                          has_aux=True))

    (_, got), g_got = loss(packed_global_attention)(qkv, rh, rw)
    (_, want), g_want = loss(_packed_global_oracle)(qkv, rh, rw)
    assert got.shape == (2 * 1024, heads * head_dim)
    pairs = [(got, want)] + list(zip(g_got, g_want))
    for a, b in pairs:
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        if dtype == "float32":
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)
        else:
            assert np.abs(a - b).max() / np.abs(b).max() < 0.05


@pytest.mark.parametrize("heads,head_dim", [(2, 64), (8, 80)])
def test_packed_global_attention_rides_q_rh_a_key_block_at_a_time(
    heads, head_dim, monkeypatch
):
    """With four key blocks an image, each holds eight grid rows, and only
    their eight entries of q.RH ride in a block's contraction, in the lanes
    the head leaves free, against the one-hots ``_global_key_rows`` puts
    beside k: the kernel still equals the oracle, and the one-hots sit
    where the layout says."""
    from tmr_tpu.ops import pallas_attn

    monkeypatch.setattr(pallas_attn, "_GLOBAL_KEY_BLOCK", 256)
    monkeypatch.setattr(pallas_attn, "_GLOBAL_QUERY_BLOCK", 256)
    assert pallas_attn._global_blocks((32, 32)) == (256, 256)
    rows = np.asarray(pallas_attn._global_key_rows(
        (32, 32), head_dim, 256, jnp.float32))
    assert rows.shape == (1024, 128)
    u = np.arange(1024)
    lane = u // 32 % 8  # a key's grid row within its block of eight
    if head_dim == 64:  # in every head's lanes; q' is zero but in one
        assert (rows.sum(1) == 2).all()
        assert rows[u, lane].all() and rows[u, 64 + lane].all()
    else:               # past the head aligned to lane 0
        assert (rows.sum(1) == 1).all() and rows[u, 80 + lane].all()
    qkv, rh, rw = _packed_global_case(1, heads, head_dim, jnp.float32)
    scale = head_dim**-0.5
    pallas_attn._packed_global_fwd_impl.clear_cache()
    try:
        got = pallas_attn.packed_global_attention(
            qkv, rh, rw, (32, 32), heads, scale)
    finally:
        pallas_attn._packed_global_fwd_impl.clear_cache()
    want = pallas_attn._packed_global_oracle(
        qkv, rh, rw, (32, 32), heads, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_packed_global_attention_reads_toeplitz_tables_only():
    """The kernel takes the 2g - 1 distinct rows of a ``get_rel_pos``
    table from its first row and first column, by the lane they are read
    from: ``_toeplitz_lanes`` puts table[y, ky] in lane (ky - y) mod 128,
    and ``_global_tables`` lays the two tables side by side, a copy for
    every head of a slab."""
    from tmr_tpu.models.vit import get_rel_pos
    from tmr_tpu.ops.pallas_attn import _global_tables, _toeplitz_lanes

    rng = np.random.default_rng(5)
    param = jnp.asarray(rng.standard_normal((63, 64)), jnp.float32)
    table = get_rel_pos(32, 32, param)
    lanes = np.asarray(_toeplitz_lanes(table))
    assert lanes.shape == (128, 64)
    for y, ky in ((0, 0), (0, 31), (31, 0), (7, 19), (19, 7)):
        np.testing.assert_array_equal(lanes[(ky - y) % 128], table[y, ky])
    assert not lanes[32:97].any()
    both = np.asarray(_global_tables(table, 2 * table, 64, jnp.float32))
    assert both.shape == (128, 256)
    np.testing.assert_array_equal(both[:64, :128], lanes.T)
    np.testing.assert_array_equal(both[64:, 128:], 2 * lanes.T)
    lone = _global_tables(get_rel_pos(32, 32, param[:, :48]), table[..., :48],
                          48, jnp.float32)  # a head aligned to lane 0
    assert not np.asarray(lone[48:]).any()


@pytest.mark.parametrize("toeplitz,verdict,causes", [
    (True, True, []), (False, False, ["forward-mismatch"])])
def test_packed_global_gate_checks_one_program_a_side(
    toeplitz, verdict, causes
):
    """``packed_global_ok``'s self-check as it runs on a chip, here with
    the backend requirement lifted (the interpreter): output and gradients
    of a side from one program, tables as ``get_rel_pos`` makes them. With
    every table entry drawn on its own the kernel, which reads the tables
    as Toeplitz, is refused on its forward: the check can tell."""
    from tmr_tpu import diagnostics
    from tmr_tpu.ops import flash_attn, pallas_attn

    diagnostics.drain_gate_refusals()
    assert flash_attn._self_check(
        pallas_attn._packed_global_on_heads, 1, 2, 32, 32, 64,
        require_tpu=False, gate="packed_global_ok", toeplitz=toeplitz,
        one_program=True) is verdict
    assert [r["cause"] for r in diagnostics.drain_gate_refusals()] == causes


def test_global_block_with_packed_equals_blockwise_module():
    """``Attention`` at 1024 tokens with ``global_formulation`` answering
    ``packed`` (the interpreter here, float32) equals the module's
    blockwise output on the same parameter tree: ``qkv`` and ``proj`` on
    rows of tokens are the same leaves."""
    from tmr_tpu.models.vit import Attention
    from tmr_tpu.obs import metrics
    from tmr_tpu.ops import pallas_attn

    attn = Attention(num_heads=2, rel_pos_size=(32, 32), dtype=jnp.float32)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 32, 32, 128)), jnp.float32)
    params = attn.init(jax.random.key(0), x)
    params = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape) * 0.1, a.dtype),
        params)
    want = jax.jit(attn.apply)(params, x)
    metrics.get_registry().reset("vit.global_attn.")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pallas_attn, "global_formulation", lambda *a: "packed")
        got = jax.jit(attn.apply)(params, x)
    assert metrics.get_registry().counters("vit.global_attn.") == {
        "packed": 1}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


#: the knobs that once picked or tuned a global block's formulation, each
#: set to a value the old code acted on or refused (the tile knobs were read
#: only under an explicit formulation, so those cases name one too); the
#: program reads none of them
_DEAD = {
    "dead-formulation": {"TMR_GLOBAL_ATTN": "blockwise"},
    "dead-scores-dtype": {"TMR_GLOBAL_ATTN": "blockfolded",
                          "TMR_GLOBAL_SCORES_DTYPE": "fp8"},
    "dead-bands-unroll": {"TMR_GLOBAL_BANDS_UNROLL": "0"},
    "dead-pallas-bq": {"TMR_GLOBAL_ATTN": "pallas",
                       "TMR_PALLAS_ATTN_BQ": "100"},
    "dead-pallas-bk": {"TMR_GLOBAL_ATTN": "fused",
                       "TMR_PALLAS_ATTN_BK": "100"},
    "dead-xla-flash-bq": {"TMR_GLOBAL_ATTN": "xlaflash",
                          "TMR_XLA_FLASH_BQ": "0"},
    "dead-xla-flash-bk": {"TMR_GLOBAL_ATTN": "xlaflash",
                          "TMR_XLA_FLASH_BK": "0"},
}


@pytest.mark.parametrize(
    "backend,dtype,gate,rel_pos,grid,head_dim,env,taken", [
        ("cpu", "bfloat16", "admits", True, 32, 64, None, "blockwise"),
        ("tpu", "bfloat16", "admits", True, 32, 64, None, "packed"),
        ("tpu", "float32", "admits", True, 32, 64, None, "blockwise"),
        ("tpu", "bfloat16", "partitioned", True, 32, 64, None, "blockwise"),
        ("tpu", "bfloat16", "refuses", True, 32, 64, None, "flash"),
        ("tpu", "bfloat16", "admits", False, 32, 64, None, "flash"),
        ("tpu", "bfloat16", "admits", True, 96, 64, None, "flash"),
        ("tpu", "bfloat16", "admits", True, 64, 80, None, "packed"),
        ("tpu", "bfloat16", "mosaic-off", True, 96, 64, None, "blockwise"),
        ("tpu", "float32", "admits", True, 96, 64, None, "blockwise"),
        ("tpu", "bfloat16", "admits", True, 32, 64,
         _DEAD["dead-formulation"], "packed"),
        ("tpu", "bfloat16", "admits", True, 32, 64,
         _DEAD["dead-scores-dtype"], "packed"),
        ("cpu", "bfloat16", "admits", True, 32, 64,
         _DEAD["dead-bands-unroll"], "blockwise"),
        ("tpu", "bfloat16", "refuses", True, 32, 64,
         _DEAD["dead-pallas-bq"], "flash"),
        ("tpu", "bfloat16", "admits", False, 32, 64,
         _DEAD["dead-pallas-bk"], "flash"),
        ("tpu", "float32", "admits", True, 32, 64,
         _DEAD["dead-xla-flash-bq"], "blockwise"),
        ("tpu", "float32", "admits", True, 32, 64,
         _DEAD["dead-xla-flash-bk"], "blockwise"),
    ], ids=["cpu-blockwise", "tpu-packed", "tpu-float32-blockwise",
            "tpu-partitioned-blockwise", "tpu-gate-refuses-flash",
            "tpu-no-rel-pos-flash", "tpu-192-projections-flash",
            "tpu-heads-of-80-packed", "tpu-mosaic-off-96-blockwise",
            "tpu-float32-96-blockwise", *_DEAD])
def test_global_blocks_are_counted_by_formulation(
    backend, dtype, gate, rel_pos, grid, head_dim, env, taken, monkeypatch
):
    """``ops/pallas_attn.global_formulation``'s truth table, and its
    record: ``vit.global_attn.<formulation>`` counts a trace's global
    blocks under what it answers and the program's ``compile`` span names
    it. ``packed`` only where the backend reads ``tpu``, the trace is
    bfloat16, the block has rel-pos tables, gh + gw fits the 128 lanes and
    the gate admits the kernel; everything else keeps the path ``auto``
    answered before: ``flash`` where that gate passes (a chip's answer,
    given here; off a TPU it refuses for itself), else ``blockwise``. Both
    gates refuse with cause ``partitioned`` inside a program XLA
    partitions and under ``diagnostics.mosaic_kernels_off``. No
    environment variable is read: with a knob of the old formulations set,
    the count and the traced program are those without it."""
    from tmr_tpu import diagnostics, obs
    from tmr_tpu.models.vit import Attention, SamViT
    from tmr_tpu.obs import metrics
    from tmr_tpu.ops import flash_attn, pallas_attn
    from tmr_tpu.parallel.compat import partitioned

    for name, value in (env or {}).items():
        monkeypatch.setenv(name, value)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    real_gates = gate in ("partitioned", "mosaic-off")
    if not real_gates:  # there the real gates answer, unasked
        monkeypatch.setattr(pallas_attn, "packed_global_ok",
                            lambda *a: gate == "admits")
        monkeypatch.setattr(pallas_attn, "packed_window_ok",
                            lambda *a: False)
        if backend == "tpu":
            monkeypatch.setattr(flash_attn, "flash_attention_ok",
                                lambda *a: True)
    dtype = jnp.dtype(dtype)
    heads = 2 if head_dim == 64 else 16
    px = grid * 16
    if rel_pos:
        blocks = 4
        model = SamViT(embed_dim=heads * head_dim, depth=12, num_heads=heads,
                       global_attn_indexes=(2, 5, 8, 11), window_size=14,
                       out_chans=8, pretrain_img_size=px, dtype=dtype)
        x = jax.ShapeDtypeStruct((1, px, px, 3), jnp.float32)
    else:
        blocks = 1
        model = Attention(num_heads=heads, use_rel_pos=False, dtype=dtype)
        x = jax.ShapeDtypeStruct((1, grid, grid, heads * head_dim), dtype)
    with diagnostics.mosaic_kernels_off("shapes only"):  # asks no gate
        params = jax.eval_shape(model.init, jax.random.key(0), x)

    def trace():
        jax.eval_shape(model.apply, params, x)
        return pallas_attn.global_formulation(
            (grid, grid), heads, head_dim, dtype, rel_pos)

    if gate == "partitioned":
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ("model",))
        trace = partitioned(trace, mesh)
    elif gate == "mosaic-off":
        bare = trace

        def trace():
            with diagnostics.mosaic_kernels_off("the test's"):
                return bare()
    diagnostics.drain_gate_refusals()
    metrics.get_registry().reset("vit.global_attn.")
    program = obs.track_compile(
        trace, "test_kind_global_attn",
        (backend, str(dtype), gate, rel_pos, grid, head_dim, str(env)))
    assert program() == taken
    assert metrics.get_registry().counters("vit.global_attn.") == {
        taken: blocks}
    span = [r for r in obs.spans() if r["name"] == "compile"
            and r["attrs"]["kind"] == "test_kind_global_attn"][-1]
    assert span["attrs"]["global_attn"] == taken
    assert span["attrs"]["global_attn_blocks"] == blocks
    causes = {(r["gate"], r["cause"])
              for r in diagnostics.drain_gate_refusals()}
    if gate == "partitioned":
        assert causes == {("packed_global_ok", "partitioned"),
                          ("flash_attention_ok", "partitioned"),
                          ("packed_window_ok", "partitioned")}
    elif gate == "mosaic-off":  # no packed layout at 96 x 96: never asked
        assert causes == {("flash_attention_ok", "partitioned"),
                          ("packed_window_ok", "partitioned")}
    elif backend == "cpu":  # the flash gate's own no, unless cached
        assert causes <= {("flash_attention_ok", "backend")}
    else:
        assert causes == set()
    if env:
        with_env = str(jax.make_jaxpr(model.apply)(params, x))
        for name in env:
            monkeypatch.delenv(name)
        assert str(jax.make_jaxpr(model.apply)(params, x)) == with_env


@pytest.mark.parametrize("module", [
    "tmr_tpu.models.vit", "tmr_tpu.ops.pallas_attn", "tmr_tpu.ops.flash_attn",
])
def test_attention_modules_read_only_the_kill_switch_and_gate_debug(module):
    """The modules on the ViT attention's path read no environment
    variable but the flash family's kill-switch (``TMR_NO_FLASH_ATTN``)
    and ``TMR_GATE_DEBUG``, each by its literal name: the formulation is
    decided by what the choosers observe."""
    import ast
    import importlib
    import inspect

    tree = ast.parse(inspect.getsource(importlib.import_module(module)))
    touches = sum(isinstance(n, ast.Attribute) and n.attr in (
        "environ", "getenv") for n in ast.walk(tree))
    names = []
    for n in ast.walk(tree):
        target = None
        if isinstance(n, ast.Call) and isinstance(n.func, ast.Attribute):
            f = n.func
            if (f.attr == "get" and isinstance(f.value, ast.Attribute)
                    and f.value.attr == "environ") or f.attr == "getenv":
                target = n.args[0]
        elif (isinstance(n, ast.Subscript)
              and isinstance(n.value, ast.Attribute)
              and n.value.attr == "environ"):
            target = n.slice
        if target is not None:
            names.append(target.value if isinstance(target, ast.Constant)
                         else ast.dump(target))
    assert len(names) == touches, (module, names, touches)
    assert set(names) <= {"TMR_NO_FLASH_ATTN", "TMR_GATE_DEBUG"}, names


@pytest.mark.slow
def test_fold_rel_pos_into_qk_exact():
    """The augmented-QK trick (ops/flash_attn.py) must reproduce the biased
    scores EXACTLY in f32: q'.k'^T == scale*q.k^T + decomposed bias."""
    import numpy as np

    from tmr_tpu.ops.flash_attn import fold_rel_pos_into_qk
    from tmr_tpu.parallel.ring import dense_attention

    rng = np.random.default_rng(3)
    B, H, gh, gw, D = 2, 2, 6, 10, 16
    S = gh * gw
    q = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, D)), jnp.float32)
    rh = jnp.asarray(rng.standard_normal((gh, gh, D)), jnp.float32) * 0.3
    rw = jnp.asarray(rng.standard_normal((gw, gw, D)), jnp.float32) * 0.3
    scale = D**-0.5

    r_q = q.reshape(B, H, gh, gw, D)
    rel_h = jnp.einsum("bnhwc,hkc->bnhwk", r_q, rh)
    rel_w = jnp.einsum("bnhwc,wkc->bnhwk", r_q, rw)
    bias = (rel_h[..., :, None] + rel_w[..., None, :]).reshape(B, H, S, S)
    want_scores = (
        jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale + bias
    )

    q_aug, k_aug = fold_rel_pos_into_qk(q, k, rh, rw, (gh, gw), scale,
                                        pad_to=128)
    assert q_aug.shape[-1] == 128 and k_aug.shape[-1] == 128
    got_scores = jnp.einsum("bhqd,bhkd->bhqk", q_aug, k_aug)
    np.testing.assert_allclose(
        np.asarray(got_scores), np.asarray(want_scores), rtol=1e-5, atol=1e-5
    )

    # end to end: softmax(q'.k') @ v == biased dense attention
    want = dense_attention(q, k, v, bias=bias, scale=scale)
    got = dense_attention(q_aug, k_aug, v, scale=1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    # no-bias variant: just scaled/padded passthrough
    q2, k2 = fold_rel_pos_into_qk(q, k, None, None, (gh, gw), scale)
    np.testing.assert_allclose(np.asarray(q2), np.asarray(q) * scale,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(k2), np.asarray(k), rtol=1e-6)


def test_flash_attention_ok_is_false_off_tpu():
    if jax.default_backend() == "tpu":  # pragma: no cover - CPU CI suite
        pytest.skip("flash path legitimately enabled on TPU")
    from tmr_tpu.ops.flash_attn import flash_attention_ok

    assert flash_attention_ok() is False  # CPU test backend -> XLA path


def test_flash_block_size_selection():
    from tmr_tpu.ops.flash_attn import _block_for, flash_supported

    assert _block_for(4096, 512) == 512
    assert _block_for(9216, 512) == 512  # 1536 bucket: 9216 = 512*18
    assert _block_for(2500, 512) is None  # 50x50 grid: no pow2 factor >=128
    assert _block_for(1024, 512) == 512
    assert _block_for(1280, 512) == 256
    assert flash_supported(4096) and not flash_supported(2500)


def test_flash_attention_ok_callable_under_trace():
    """flash_attention_ok is invoked while TRACING the model; it must not
    leak tracers or poison its cache when first called inside jit."""
    if jax.default_backend() == "tpu":  # pragma: no cover - CPU CI suite
        pytest.skip("flash path legitimately enabled on TPU")
    from tmr_tpu.ops.flash_attn import flash_attention_ok

    flash_attention_ok.cache_clear()
    seen = []

    @jax.jit
    def traced(x):
        seen.append(flash_attention_ok())  # trace-time call
        return x + 1

    traced(jnp.zeros((2,)))
    assert seen == [False]  # CPU backend -> disabled, but no exception/tracer
    flash_attention_ok.cache_clear()


@pytest.mark.slow
def test_flash_windowed_padding_and_segments(monkeypatch):
    """flash_windowed_attention pads 196-token windows to 256 and masks the
    pad via a second segment. The Pallas kernel itself needs a TPU, but its
    module ships mha_reference with identical (q, k, v, ab, segment_ids)
    semantics — swapping it in validates the fold/pad/segment construction
    end to end on CPU."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa_mod

    from tmr_tpu.ops import flash_attn
    from tmr_tpu.models.vit import blockwise_decomposed_attention

    def stub(q, k, v, ab=None, segment_ids=None, causal=False, sm_scale=1.0,
             block_sizes=None, debug=False):
        return fa_mod.mha_reference(
            q, k, v, ab, segment_ids, causal=causal, sm_scale=sm_scale
        )

    monkeypatch.setattr(fa_mod, "flash_attention", stub)

    rng = np.random.default_rng(7)
    b, hds, gh, gw, d = 3, 2, 14, 14, 16
    s = gh * gw
    mk = lambda: jnp.asarray(rng.standard_normal((b, hds, s, d)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    rh = jnp.asarray(rng.standard_normal((gh, gh, d)) * 0.2, jnp.float32)
    rw = jnp.asarray(rng.standard_normal((gw, gw, d)) * 0.2, jnp.float32)
    scale = d**-0.5

    got = flash_attn.flash_windowed_attention(q, k, v, rh, rw, (gh, gw), scale)
    want = blockwise_decomposed_attention(q, k, v, rh, rw, (gh, gw), scale)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )
    assert got.shape == (b, hds, s, d)


@pytest.mark.slow
def test_flash_self_check_harness_including_grads(monkeypatch):
    """_self_check gates the flash paths on TPU (forward AND backward since
    the train step differentiates through them). Off-TPU it must refuse;
    with the backend gate and kernel stubbed it must pass end to end,
    proving the harness itself (jit compare + grad compare) is sound."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa_mod

    from tmr_tpu.ops import flash_attn

    if jax.default_backend() == "tpu":
        pytest.skip("gate legitimately runs the real kernel on TPU")
    monkeypatch.delenv("TMR_NO_FLASH_ATTN", raising=False)

    # real backend (cpu): the gate refuses outright
    assert flash_attn._self_check(
        flash_attn.flash_windowed_attention, 1, 1, 7, 7, 8
    ) is False

    def stub(q, k, v, ab=None, segment_ids=None, causal=False, sm_scale=1.0,
             block_sizes=None, debug=False):
        return fa_mod.mha_reference(
            q, k, v, ab, segment_ids, causal=causal, sm_scale=sm_scale
        )

    monkeypatch.setattr(fa_mod, "flash_attention", stub)
    monkeypatch.setattr(flash_attn.jax, "default_backend", lambda: "tpu")
    assert flash_attn._self_check(
        flash_attn.flash_windowed_attention, 1, 1, 7, 7, 8
    ) is True
    # a broken kernel must be caught, not crash the trace
    monkeypatch.setattr(
        fa_mod, "flash_attention",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("mosaic")),
    )
    assert flash_attn._self_check(
        flash_attn.flash_windowed_attention, 1, 1, 7, 7, 8
    ) is False


def test_flash_self_check_rejects_nan(monkeypatch):
    """A Mosaic miscompile classically surfaces as NaN output; the gate must
    reject it (comparisons are phrased so NaN fails, never passes)."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa_mod

    from tmr_tpu.ops import flash_attn

    if jax.default_backend() == "tpu":
        pytest.skip("gate legitimately runs the real kernel on TPU")
    monkeypatch.delenv("TMR_NO_FLASH_ATTN", raising=False)
    monkeypatch.setattr(flash_attn.jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        fa_mod, "flash_attention",
        lambda q, *a, **k: jnp.full_like(q, jnp.nan),
    )
    assert flash_attn._self_check(
        flash_attn.flash_windowed_attention, 1, 1, 7, 7, 8
    ) is False


def test_flash_supported_production_lengths():
    """Block constraints hold at both production buckets (4096 = 64x64,
    9216 = 96x96 has the 2^10 factor) and fail at the window length."""
    from tmr_tpu.ops.flash_attn import flash_supported

    assert flash_supported(4096)
    assert flash_supported(9216)
    assert not flash_supported(196)  # windows go through the padded path


@pytest.mark.slow
def test_ring_at_1536_bucket_scale():
    """The 1536 small-object bucket is the reference's longest sequence
    (96x96 = 9216 tokens, sam.py:72-76 pos-embed re-interpolation); ring
    attention must hold exactly there — per-device KV slabs of 9216/8
    tokens, online-softmax accumulation over 8 ppermute hops. Small head
    count keeps the dense oracle affordable on CPU."""
    b, h, s, d = 1, 2, 96 * 96, 16
    rng = np.random.default_rng(42)
    mk = lambda: jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    q, k, v = mk(), mk(), mk()
    want = dense_attention(q, k, v)

    mesh = seq_mesh(8)
    fn = shard_map(
        lambda q, k, v: ring_attention(q, k, v, "seq"),
        mesh=mesh, in_specs=(SEQ_SPEC,) * 3,
        out_specs=SEQ_SPEC, check_vma=False,
    )
    got = jax.jit(fn)(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
    )
