"""Mesh-sharded serving tier (tmr_tpu/serve/meshplan + the sharded
program variants): ragged-tail exactness under dp sharding, mesh-shape-
change recompile keys, AOT warmup's zero-cold-compile pin, per-replica-
group queues/health, the per-chip MFU division, and the admission
drain-rate capacity signal — all on conftest's forced-8-device CPU mesh.

The load-bearing contract: a dp mesh's shard_map per-shard trace IS the
unsharded program body at the local batch shape, so dp-sharded serve
results are BITWISE-identical to sequential Predictor calls (tp
programs are allclose with identical keep decisions — collectives
reorder float reductions, the documented heads-path-style exception).
"""

import numpy as np
import pytest

SIZE = 128

SMALL_EX = np.asarray([[0.45, 0.45, 0.53, 0.55]], np.float32)  # cap 9
BIG_EX = np.asarray([[0.1, 0.1, 0.9, 0.9]], np.float32)  # cap 17
MULTI_EX = np.asarray(
    [[0.45, 0.45, 0.53, 0.55], [0.2, 0.2, 0.28, 0.3],
     [0.6, 0.55, 0.68, 0.66]], np.float32,
)
FIELDS = ("boxes", "scores", "refs", "valid")


def _img(seed):
    return np.random.default_rng(seed).standard_normal(
        (SIZE, SIZE, 3)
    ).astype(np.float32)


def _np(dets):
    return {k: np.asarray(dets[k]) for k in FIELDS}


def _assert_bitwise(a, b, ctx=""):
    for k in FIELDS:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), (
            f"{ctx}: field {k!r} not bitwise-identical"
        )


@pytest.fixture(scope="module")
def pred():
    from tmr_tpu.config import preset
    from tmr_tpu.inference import Predictor

    cfg = preset("TMR_FSCD147", backbone="sam_vit_b", image_size=SIZE,
                 compute_dtype="float32", batch_size=1)
    p = Predictor(cfg)
    p.init_params(seed=0, image_size=SIZE)
    return p


# ----------------------------------------------------------- mesh specs
def test_parse_mesh_spec():
    from tmr_tpu.parallel.mesh import parse_mesh_spec

    assert parse_mesh_spec("dp4") == {"dp": 4, "tp": 1}
    assert parse_mesh_spec("tp4") == {"dp": 1, "tp": 4}
    assert parse_mesh_spec("dp2tp2") == {"dp": 2, "tp": 2}
    assert parse_mesh_spec("tp2dp4") == {"dp": 4, "tp": 2}
    for bad in ("", "dp", "dp0", "pp2", "dp2dp2", "dp2 tp2", "2"):
        with pytest.raises(ValueError):
            parse_mesh_spec(bad)


def test_mesh_plan_groups_policy_and_describe():
    import jax

    from tmr_tpu.serve.meshplan import MeshPlan, resolve_plan

    plan = MeshPlan("dp2tp2", devices=jax.devices(), tp_size=512)
    assert plan.dp == 2 and plan.tp == 2
    assert len(plan.group_targets) == 2
    assert all(t.n_devices == 2 for t in plan.group_targets)
    assert plan.dp_target is not None and plan.dp_target.n_devices == 4
    # replica groups partition the leading 4 devices, disjoint
    devs = [d for t in plan.group_targets for d in t.devices]
    assert len(set(devs)) == 4
    # per-bucket mode: small images fan out dp, big ones go tp on a
    # group, heads always per group
    assert plan.mode_for(("single", 128, 9, 1)) == "dp"
    assert plan.mode_for(("single", 512, 17, 1)) == "group"
    assert plan.mode_for(("heads", 128, 9, 1)) == "group"
    assert plan.group_ids() == ["group0", "group1", "dp"]
    # the mesh attachment validates inside a serve_report
    from tmr_tpu.diagnostics import SERVE_REPORT_SCHEMA
    from tmr_tpu.diagnostics import validate_serve_report

    doc = {"schema": SERVE_REPORT_SCHEMA, "error": "x",
           "mesh": plan.describe()}
    assert validate_serve_report(doc) == []
    doc["mesh"]["replica_groups"] = []
    assert any("replica_groups" in p for p in validate_serve_report(doc))
    # unset/off specs resolve to no plan; a typo raises
    assert resolve_plan(None) is None or True  # env-dependent guard
    assert resolve_plan("") is None
    assert resolve_plan("off") is None
    with pytest.raises(ValueError):
        resolve_plan("dp2xx")


def test_mesh_plan_rejects_oversized_and_misfit():
    import jax

    from tmr_tpu.serve.meshplan import MeshPlan

    with pytest.raises(ValueError):
        MeshPlan("dp16", devices=jax.devices())  # 8 forced devices
    from tmr_tpu.parallel.sharding import validate_tp

    plan = MeshPlan("tp2", devices=jax.devices())
    validate_tp(plan.group_targets[0].mesh, 768, 12, axis="tp")  # fits
    with pytest.raises(ValueError):
        validate_tp(plan.group_targets[0].mesh, 768, 13, axis="tp")


# ------------------------------------------------- grouped micro-batcher
def test_grouped_batcher_queues_depths_and_occupancy():
    from tmr_tpu.serve import MicroBatcher, Request

    b = MicroBatcher(max_wait_ms=5000, bound_for=lambda bucket: 2,
                     groups=["g0", "g1"])
    for i in range(2):
        b.put(Request(image=None, exemplars=None, bucket=("x",),
                      group="g0"))
    b.put(Request(image=None, exemplars=None, bucket=("x",), group="g1"))
    by_group = b.depth_by_group()
    assert by_group["g0"]["pending"] == 2
    assert by_group["g1"]["pending"] == 1
    assert by_group["g0"]["per_bucket"] == {("x",): 2}
    # merged per-bucket view sums groups
    assert b.depth_snapshot() == {("x",): 3}
    # g1's consumer sees only g1's traffic (g0 is full, g1 is not)
    bucket, reqs = b.next_batch(group="g0")
    assert bucket == ("x",) and len(reqs) == 2
    assert b.occupancy_snapshot(group="g0") == {2: 1}
    assert b.occupancy_snapshot(group="g1") == {}
    # a grouped batcher refuses ungrouped pops and unknown groups
    with pytest.raises(ValueError):
        b.next_batch()
    with pytest.raises(ValueError):
        b.put(Request(image=None, exemplars=None, bucket=("x",),
                      group="nope"))
    b.close()
    bucket, reqs = b.next_batch(group="g1")  # drain
    assert len(reqs) == 1
    assert b.next_batch(group="g1") is None
    assert b.next_batch(group="g0") is None


def test_ungrouped_batcher_rejects_grouped_pop():
    from tmr_tpu.serve import MicroBatcher

    b = MicroBatcher(max_wait_ms=10, bound_for=lambda bucket: 2)
    with pytest.raises(ValueError):
        b.next_batch(group="g0")


# ------------------------------------------------ per-group health watch
def test_healthwatch_fires_queue_saturation_per_group():
    from tmr_tpu.obs.flight import HealthWatch

    w = HealthWatch(queue_depth_threshold=8)
    fired = w.observe({}, pending=100,
                      pending_by_group={"group0": 100, "group1": 0})
    sat = [r for r in fired if r["anomaly"] == "queue_saturation"]
    assert len(sat) == 1
    assert sat[0]["evidence"]["group"] == "group0"
    assert sat[0]["evidence"]["pending"] == 100
    # two saturated groups fire two records, one each
    fired = w.observe({}, pending=64,
                      pending_by_group={"group0": 32, "group1": 32})
    sat = [r for r in fired if r["anomaly"] == "queue_saturation"]
    assert {r["evidence"]["group"] for r in sat} == {"group0", "group1"}
    # ungrouped callers keep the single global record
    fired = w.observe({}, pending=100)
    sat = [r for r in fired if r["anomaly"] == "queue_saturation"]
    assert len(sat) == 1 and "group" not in sat[0]["evidence"]


# ------------------------------------------- admission capacity signal
def test_admission_drain_source_overrides_window():
    from tmr_tpu.serve.admission import AdmissionController

    ctl = AdmissionController(enabled=True, max_pending=1)
    ctl.attach_drain_source(lambda: 2.0)
    assert ctl.stats()["drain_per_sec"] == 2.0
    assert ctl.try_admit() is None
    rej = ctl.try_admit()  # bound hit: retry_after from the 2/s signal
    assert rej is not None and rej.cause == "queue_full"
    assert rej.retry_after_s == pytest.approx(1.0 / 2.0, rel=0.2)
    # a broken source falls back to the internal window, never raises
    ctl.attach_drain_source(lambda: (_ for _ in ()).throw(RuntimeError()))
    assert ctl.stats()["drain_per_sec"] == 0.0


# ------------------------------------------------- dp ragged exactness
def _mixed_requests(n):
    reqs = []
    for i in range(n):
        img = _img(300 + i)
        if i % 3 == 2:
            reqs.append((img, MULTI_EX, True))
        else:
            reqs.append((img, BIG_EX if i % 2 else SMALL_EX, False))
    return reqs


def _sequential(pred, reqs):
    out = []
    for img, ex, multi in reqs:
        if multi:
            out.append(_np(pred.predict_multi_exemplar(img[None], ex)))
        else:
            out.append(_np(pred(img[None], ex[None])))
    return out


@pytest.mark.parametrize("n", [1, 4, 5])
def test_dp_ragged_tail_bitwise_vs_unsharded(pred, n):
    """N mixed requests (two capacities + a multi-exemplar rider)
    through a dp2 mesh engine == N sequential Predictor calls, BITWISE:
    the shard_map per-shard trace is the unsharded program body at the
    local batch shape, so sharding is invisible in the bytes."""
    from tmr_tpu.serve import ServeEngine

    reqs = _mixed_requests(n)
    seq = _sequential(pred, reqs)
    with ServeEngine(pred, batch=1, max_wait_ms=20, feature_cache=0,
                     exemplar_cache=0, mesh="dp2") as eng:
        futs = [eng.submit(img, ex, multi=multi)
                for img, ex, multi in reqs]
        results = [f.result(timeout=600) for f in futs]
        stats = eng.stats()
    assert stats["errors"] == 0
    assert stats["mesh"]["shape"] == {"dp": 2, "tp": 1}
    for i, (a, b) in enumerate(zip(seq, results)):
        _assert_bitwise(a, b, ctx=f"dp2 request {i} of {n}")


def test_tp_group_parity_and_per_group_stats(pred):
    """A tp2 replica group runs the tensor-parallel program: identical
    keep decisions, floats at allclose (TP collectives reorder
    reductions — documented), per-group sections in stats()/health()."""
    from tmr_tpu.diagnostics import validate_health_report
    from tmr_tpu.serve import ServeEngine

    img = _img(400)
    ref = _np(pred(img[None], SMALL_EX[None]))
    with ServeEngine(pred, batch=1, max_wait_ms=20, feature_cache=0,
                     exemplar_cache=0, mesh="tp2") as eng:
        r = eng.submit(img, SMALL_EX).result(timeout=600)
        stats = eng.stats()
        health = eng.health()
    assert np.array_equal(ref["valid"], np.asarray(r["valid"]))
    for k in ("boxes", "scores", "refs"):
        assert np.allclose(ref[k].astype(np.float64),
                           np.asarray(r[k]).astype(np.float64),
                           atol=1e-4), k
    assert stats["mesh"]["shape"] == {"dp": 1, "tp": 2}
    assert list(stats["per_group_queues"]) == ["group0"]
    assert validate_health_report(health) == []
    assert "group0" in health["queues"]["per_group"]
    assert "drain_per_group" in health


def test_mesh_shape_change_recompiles_no_key_collision(pred):
    """The _compiled keys embed the mesh shape + device ids: a dp2 and
    a dp4 engine over the same Predictor compile DISTINCT sharded
    entries (no silent collision serving dp4 traffic through a dp2
    executable), and both serve bitwise-correct results."""
    from tmr_tpu.serve import ServeEngine

    img = _img(500)
    ref = _np(pred(img[None], SMALL_EX[None]))

    def sharded_keys():
        return {k for k in pred._compiled
                if isinstance(k, tuple) and k and
                k[0] == "single_sharded"}

    with ServeEngine(pred, batch=1, max_wait_ms=20, feature_cache=0,
                     exemplar_cache=0, mesh="dp2") as eng:
        _assert_bitwise(ref, eng.submit(img, SMALL_EX).result(
            timeout=600), ctx="dp2")
    keys_dp2 = sharded_keys()
    assert keys_dp2, "dp2 compiled no sharded entry"
    with ServeEngine(pred, batch=1, max_wait_ms=20, feature_cache=0,
                     exemplar_cache=0, mesh="dp4") as eng:
        _assert_bitwise(ref, eng.submit(img, SMALL_EX).result(
            timeout=600), ctx="dp4")
    keys_dp4 = sharded_keys() - keys_dp2
    assert keys_dp4, "dp4 reused the dp2 executable (key collision)"
    # the dp2 entries survived — a shape change is a NEW entry, not an
    # overwrite of the old one
    assert keys_dp2 <= sharded_keys()


def test_aot_warmup_records_zero_cold_compiles_after_start(pred):
    """Engine start AOT-warms every (bucket, mesh-shape) program in the
    declared set; steady-state traffic then records ZERO new compile
    events (PR 8's compile-event cursor — the serve_bench --mesh
    acceptance pin, here in-process)."""
    from tmr_tpu import obs
    from tmr_tpu.serve import ServeEngine

    bucket = pred.bucket_key(SIZE, SMALL_EX)
    eng = ServeEngine(pred, batch=1, max_wait_ms=20, feature_cache=0,
                      exemplar_cache=0, mesh="dp2",
                      warmup_buckets=[bucket], aot=True)
    try:
        stats = eng.stats()
        assert stats["warmup"]["programs"] >= 1
        assert stats["warmup"]["skipped"] == 0
        cursor = obs.compile_event_seq()
        futs = [eng.submit(_img(600 + i), SMALL_EX) for i in range(3)]
        for f in futs:
            f.result(timeout=600)
        new, _seq = obs.compile_events_since(cursor)
        assert new == [], f"cold compiles after warmup: {new}"
    finally:
        eng.close()


def test_aot_disabled_by_env_flag(pred, monkeypatch):
    from tmr_tpu.serve import ServeEngine

    monkeypatch.setenv("TMR_SERVE_AOT", "0")
    eng = ServeEngine(pred, batch=1, max_wait_ms=20, feature_cache=0,
                      exemplar_cache=0, mesh="dp2")
    try:
        assert eng._warmup_stats is None
        assert "warmup" not in eng.stats()
    finally:
        eng.close()


# -------------------------------------------------- per-chip MFU (mfu)
def test_devtime_divides_mfu_by_replica_group_size():
    """Satellite pin (forced-8-device): a program tracked as spanning 8
    devices reports per-chip MFU exactly 1/8 of the same timings
    tracked single-device — tensor parallelism must not read N×
    inflated."""
    import jax
    import jax.numpy as jnp

    from tmr_tpu.obs import devtime, flight

    flight.configure(enabled=True)
    try:
        devtime.reset()

        @jax.jit
        def f(x):
            return x @ x

        x = jnp.ones((64, 64), jnp.float32)
        one = devtime.track_devtime(f, "single", ("mfu1",), devices=1)
        eight = devtime.track_devtime(f, "single", ("mfu8",), devices=8)
        for _ in range(3):  # first call per wrapper buckets as warmup
            jax.block_until_ready(one(x))
            jax.block_until_ready(eight(x))
        rep = devtime.mfu_report()
        progs = {p["key"]: p for p in rep["programs"]}
        p1, p8 = progs["('mfu1',)"], progs["('mfu8',)"]
        assert p1["devices"] == 1 and p8["devices"] == 8
        assert p1["mfu"] is not None and p8["mfu"] is not None
        # identical flops; the 8-device entry divides by its group size
        # (timings differ only by measurement noise — compare each
        # entry's achieved/mfu relation, to the report's own rounding)
        peak = rep["platform"]["peak_tflops"]
        assert p8["mfu"] == pytest.approx(
            p8["achieved_tflops"] / (8 * peak), rel=0.05
        )
        assert p1["mfu"] == pytest.approx(
            p1["achieved_tflops"] / peak, rel=0.05
        )
    finally:
        devtime.reset()
        flight.configure(enabled=False)


# ----------------------------------------------- sharded program audit
def test_program_audit_covers_sharded_backbone():
    """The shard_map dp serve variant is audited trace-only like every
    production program: no f64, no host callbacks, and no device_put
    equation (a host hop written into the program; override via
    analysis_baseline.json transfer_guard for an understood one)."""
    from tmr_tpu.analysis.program_audit import audit_production_programs

    rec = audit_production_programs(
        image_size=64, max_detections=64, batch=2,
        programs=("match_heads_dp",), include_attention=False,
    )
    progs = rec["states"][0]["programs"]
    assert [p["name"] for p in progs] == ["match_heads_dp"]
    audit = progs[0]
    assert audit["ok"], audit["problems"]
    assert audit["f64_eqns"] == 0
    assert audit["callbacks"] == 0
    assert audit["transfer_pin"] == 0


def test_cached_subslice_tp_plan_is_refused_on_tpu(monkeypatch):
    """On a TPU with the persistent compile cache on, a plan with a
    multi-chip replica group that does not include the first device is an
    error at engine construction (loaded from the cache, ``tp2`` on chips
    2 and 3 halted them, on chips 0 and 1 it ran: PR 23); every other
    case passes."""
    import jax

    from tmr_tpu.serve.meshplan import MeshPlan, refuse_cached_subslice_tp

    devices = jax.devices()[:4]
    both, dp_only = MeshPlan("dp2tp2", devices), MeshPlan("dp2", devices)
    tp_first = MeshPlan("tp2", devices[:2])
    tp_later = MeshPlan("tp2", devices[2:])
    refuse_cached_subslice_tp(both)  # the CPU backend: nothing to refuse
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.config.jax_compilation_cache_dir  # conftest turned it on
    with pytest.raises(RuntimeError, match="group1 runs on chips"):
        refuse_cached_subslice_tp(both)
    with pytest.raises(RuntimeError, match="JAX_ENABLE_COMPILATION_CACHE"):
        refuse_cached_subslice_tp(tp_later)
    refuse_cached_subslice_tp(tp_first)
    refuse_cached_subslice_tp(MeshPlan("tp4", devices))
    refuse_cached_subslice_tp(dp_only)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        refuse_cached_subslice_tp(both)
        refuse_cached_subslice_tp(tp_later)
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
