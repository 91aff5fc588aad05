"""A gate's self-check result, kept beside the compile cache
(``diagnostics.mosaic_gate`` / ``run_outside_trace``): a made-up gate
whose check counts its calls, a temporary directory as the cache and a
made-up source tree as what is digested; the backend the mechanism sees
is patched to a TPU's, since off the chip nothing is kept."""

import json
import os
import shutil

import jax
import pytest

from tmr_tpu import diagnostics, obs
from tmr_tpu.diagnostics import (
    drain_gate_refusals, gate_refused, mosaic_gate, mosaic_kernels_off,
    run_outside_trace,
)
from tmr_tpu.utils import cache

IDENTITY = {
    "backend": "tpu", "device_kind": "TPU v5 lite", "jax": "0.9.0",
    "jaxlib": "0.9.0", "platform_version": "libtpu built on a Tuesday",
}


def write_sources(root) -> str:
    for name, text in (
            ("diagnostics.py", "# the gates' own file\n"),
            ("ops/kernel.py", "BLOCK = 128  # reads TMR_GLOBAL_BANDS_UNROLL "
             "and TMR_GATE_DEBUG\n"),
            ("models/oracle.py", "def oracle(x):\n    return x\n"),
            ("serve/engine.py", "# not a gate's business\n")):
        os.makedirs(os.path.dirname(os.path.join(root, name)), exist_ok=True)
        with open(os.path.join(root, name), "w") as f:
            f.write(text)
    return str(root)


@pytest.fixture
def machine(tmp_path, monkeypatch):
    """A chip machine's stand-in: (cache directory, source tree)."""
    where = tmp_path / "jax_cache"
    where.mkdir()
    source = write_sources(tmp_path / "checkout" / "tmr_tpu")
    monkeypatch.setattr(cache, "persistent_cache_dir", lambda: str(where))
    monkeypatch.setattr(diagnostics, "_backend_identity",
                        lambda: dict(IDENTITY))
    monkeypatch.setattr(diagnostics, "_PACKAGE_DIR", source)
    monkeypatch.delenv("TMR_GATE_DEBUG", raising=False)
    monkeypatch.delenv("TMR_GLOBAL_BANDS_UNROLL", raising=False)
    diagnostics._source_digest.cache_clear()
    drain_gate_refusals()
    yield str(where), source
    diagnostics._source_digest.cache_clear()
    drain_gate_refusals()


def new_process(calls: list, result=True, tolerance: float = 0.02):
    """A fresh wrapper of the same gate: what a new process has. ``result``
    is what its check returns (called first, if it can be)."""

    @mosaic_gate
    def made_up_ok(n: int = 1) -> bool:
        if os.environ.get("TMR_NO_FLASH_ATTN"):
            return gate_refused("made_up_ok", "kill-switch", "kill-switch")

        def check():
            calls.append(n)
            return result() if callable(result) else result

        try:
            got = run_outside_trace(check, "made_up_ok")
        except Exception as e:
            return gate_refused("made_up_ok", str(e), "exception",
                                exception=type(e).__name__)
        if got is False or not (got is True or got < tolerance):
            return gate_refused("made_up_ok", f"gap {got}",
                                "forward-mismatch")
        return True

    return made_up_ok


def kept(where: str) -> list:
    return sorted(os.listdir(where))


def test_the_check_runs_once_a_machine(machine):
    where, _ = machine
    calls = []
    first = new_process(calls)
    assert first() is True and first() is True and calls == [1]
    (name,) = kept(where)  # one file, and no temporary name left behind
    assert name.startswith("tmr-gate-made_up_ok-") and name.endswith(".json")
    assert new_process(calls)() is True and calls == [1]
    assert new_process(calls)(n=1) is True and calls == [1]  # the default
    assert kept(where) == [name]


def test_the_file_is_plain_and_names_no_place_nor_time(machine):
    where, source = machine
    new_process([])(3)
    (name,) = kept(where)
    assert not name.endswith(("-cache", "-atime"))
    text = open(os.path.join(where, name)).read()
    doc = json.loads(text)
    assert doc["result"] is True
    assert doc["key"] == {
        "schema": "tmr_gate_result/v1", "gate": "made_up_ok",
        "args": {"n": "3"}, **IDENTITY, "env": {},
        "source": diagnostics._source_digest(source)[0]}
    assert where not in text and source not in text
    assert str(os.getpid()) not in name and len(text) < 1000


@pytest.mark.parametrize("part", [
    "argument", "device_kind", "jax", "jaxlib", "platform_version",
    "source_byte", "source_file", "knob"])
def test_each_part_of_the_key_alone_asks_again(machine, monkeypatch, part):
    where, source = machine
    calls = []
    assert new_process(calls)(1) is True and calls == [1]
    n = 1
    if part == "argument":
        n = 2
    elif part == "source_byte":
        with open(os.path.join(source, "models/oracle.py"), "a") as f:
            f.write("#")
    elif part == "source_file":
        os.rename(os.path.join(source, "ops/kernel.py"),
                  os.path.join(source, "ops/kernel2.py"))
    elif part == "knob":  # a trace-time knob the digested source reads
        monkeypatch.setenv("TMR_GLOBAL_BANDS_UNROLL", "2")
    else:
        monkeypatch.setattr(
            diagnostics, "_backend_identity",
            lambda: {**IDENTITY, part: IDENTITY[part] + "+1"})
    diagnostics._source_digest.cache_clear()  # a new process digests anew
    assert new_process(calls)(n) is True and calls == [1, n]
    assert len(kept(where)) == 2
    assert new_process(calls)(n) is True and calls == [1, n]


def test_what_changes_no_program_is_not_in_the_key(machine, monkeypatch):
    where, source = machine
    calls = []
    assert new_process(calls)() is True
    moved = os.path.join(os.path.dirname(os.path.dirname(source)), "moved")
    shutil.copytree(source, moved)  # another checkout, the same contents
    with open(os.path.join(moved, "serve/engine.py"), "a") as f:
        f.write("# an edit to what no check runs\n")
    monkeypatch.setattr(diagnostics, "_PACKAGE_DIR", moved)
    monkeypatch.setenv("TMR_GATE_DEBUG", "1")  # in the source, and blind
    assert new_process(calls)() is True and calls == [1]
    assert len(kept(where)) == 1


def boom():
    raise RuntimeError("the machine's, perhaps")


@pytest.mark.parametrize("result,cause", [
    (False, "forward-mismatch"), (boom, "exception"),
    (float("nan"), "forward-mismatch"), (float("inf"), "forward-mismatch")])
def test_only_what_passed_is_kept(machine, result, cause):
    where, _ = machine
    calls = []
    assert new_process(calls, result)() is False
    assert kept(where) == []
    assert [r["cause"] for r in drain_gate_refusals()] == [cause]
    assert new_process(calls, result)() is False and calls == [1, 1]
    assert [r["cause"] for r in drain_gate_refusals()] == [cause]


def test_a_number_is_held_to_the_tolerance_on_the_way_back(machine):
    where, _ = machine
    calls = []
    assert new_process(calls, 0.5, tolerance=1.0)() is True
    (name,) = kept(where)
    assert json.load(open(os.path.join(where, name)))["result"] == 0.5
    assert new_process(calls, 0.5, tolerance=1.0)() is True
    drain_gate_refusals()
    assert new_process(calls, 0.5, tolerance=0.02)() is False
    assert calls == [1]  # the refusal is the gate's, from the kept number
    (refusal,) = drain_gate_refusals()
    assert refusal["cause"] == "forward-mismatch"
    assert refusal["message"] == "gap 0.5"


@pytest.mark.parametrize("text", [
    "", '{"key": {"schema": "tmr_gate', "[true]", '{"result": true}',
    '{"key": {"gate": "made_up_ok"}, "result": true}', "KEY false",
    "KEY \"yes\"", "KEY NaN", "\x00\xff\x00"])
def test_a_torn_or_foreign_file_is_a_miss(machine, text):
    where, _ = machine
    calls = []
    assert new_process(calls)() is True
    (name,) = kept(where)
    path = os.path.join(where, name)
    if text.startswith("KEY "):  # this key, with what is no pass
        key = json.dumps(json.load(open(path))["key"])
        text = '{"key": %s, "result": %s}' % (key, text[4:])
    with open(path, "wb") as f:
        f.write(text.encode("latin-1"))
    assert new_process(calls)() is True and calls == [1, 1]
    assert json.load(open(path))["result"] is True  # and it is repaired
    assert new_process(calls)() is True and calls == [1, 1]


def test_a_directory_that_cannot_be_written_is_a_warning(
        machine, monkeypatch):
    where, _ = machine
    blocked = os.path.join(where, "a_file")
    open(blocked, "w").close()
    monkeypatch.setattr(cache, "persistent_cache_dir",
                        lambda: os.path.join(blocked, "under_it"))
    calls = []
    with pytest.warns(UserWarning, match="gate result not kept"):
        assert new_process(calls)() is True
        assert new_process(calls, 0.01)() is True and calls == [1, 1]
    assert kept(where) == ["a_file"]


def test_without_a_cache_directory_nothing_is_kept(machine, monkeypatch):
    where, _ = machine
    monkeypatch.setattr(cache, "persistent_cache_dir", lambda: None)
    calls = []
    assert new_process(calls)() is True and new_process(calls)() is True
    assert calls == [1, 1] and kept(where) == []


def test_the_directory_is_the_compile_caches_own(tmp_path):
    # conftest.py turned the suite's cache on at <repo>/.jax_cache
    before = jax.config.jax_compilation_cache_dir
    assert cache.persistent_cache_dir() == before == cache.DEFAULT_DIR
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert cache.persistent_cache_dir() == str(tmp_path)
        jax.config.update("jax_enable_compilation_cache", False)
        assert cache.persistent_cache_dir() is None
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", None)
        assert cache.persistent_cache_dir() is None
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", before)


def test_cache_clear_checks_afresh_and_replaces_the_file(machine):
    where, _ = machine
    calls = []
    assert new_process(calls, 0.01)() is True
    (name,) = kept(where)
    path = os.path.join(where, name)
    gate = new_process(calls, 0.015)
    assert gate() is True and calls == [1]  # from disk
    gate.cache_clear()
    assert gate() is True and gate() is True and calls == [1, 1]
    assert json.load(open(path))["result"] == 0.015
    assert gate(2) is True and calls == [1, 1, 2]
    failing = new_process(calls, False)
    failing.cache_clear()
    assert failing() is False and calls == [1, 1, 2, 1]
    assert not os.path.exists(path)  # what it found: no pass any more
    assert len(kept(where)) == 1  # the other argument's stays


def test_what_a_gate_decides_ahead_of_its_check_stays_live(
        machine, monkeypatch):
    where, _ = machine
    calls = []
    assert new_process(calls)() is True and len(kept(where)) == 1
    drain_gate_refusals()
    with mosaic_kernels_off("a partitioned trace"):
        assert new_process(calls)() is False
    monkeypatch.setenv("TMR_NO_FLASH_ATTN", "1")
    assert new_process(calls)() is False
    assert [r["cause"] for r in drain_gate_refusals()] == [
        "partitioned", "kill-switch"]
    assert calls == [1] and len(kept(where)) == 1
    monkeypatch.delenv("TMR_NO_FLASH_ATTN")
    assert new_process(calls)() is True and calls == [1]


def selfchecks() -> list:
    return [r["attrs"] for r in obs.spans()
            if r["name"] == "gate.selfcheck"
            and r["attrs"]["gate"] == "made_up_ok"]


def test_the_span_and_the_counters_say_which_it_was(machine, capsys,
                                                   monkeypatch):
    where, _ = machine
    monkeypatch.setenv("TMR_GATE_DEBUG", "1")
    counters = lambda: [
        obs.get_registry().counter(f"gate.result.{s}").value
        for s in ("check", "disk")]
    n_spans, (checks, disks) = len(selfchecks()), counters()
    new_process([])()
    assert counters() == [checks + 1, disks]
    new_process([])()
    new_process([])()
    assert counters() == [checks + 1, disks + 2]
    assert selfchecks()[n_spans:] == [
        {"gate": "made_up_ok", "source": "check"},
        {"gate": "made_up_ok", "source": "disk"},
        {"gate": "made_up_ok", "source": "disk"}]
    (name,) = kept(where)
    lines = [l for l in capsys.readouterr().err.splitlines()
             if l.startswith("[gate] made_up_ok")]
    assert [l.split(",")[0] for l in lines] == [
        "[gate] made_up_ok: self-check ran",
        "[gate] made_up_ok: answered from disk",
        "[gate] made_up_ok: answered from disk"]
    assert all(l.endswith(name) for l in lines)


def test_off_the_chip_nothing_is_written_or_taken(machine, monkeypatch):
    where, _ = machine
    calls = []
    assert new_process(calls)() is True and len(kept(where)) == 1
    monkeypatch.undo()  # the backend is the CPU it is; the cache the suite's
    assert jax.default_backend() == "cpu"
    # what tests do to lift a gate's backend test makes no chip of a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert diagnostics._backend_identity() is None
    monkeypatch.setattr(cache, "persistent_cache_dir", lambda: where)
    n_spans = len(selfchecks())
    assert new_process(calls)() is True and new_process(calls)() is True
    assert calls == [1, 1, 1] and len(kept(where)) == 1
    assert selfchecks()[n_spans:] == [
        {"gate": "made_up_ok", "source": "check"}] * 2


def test_a_bare_call_keeps_nothing(machine):
    where, _ = machine
    calls = []
    check = lambda: calls.append(0) or True
    assert run_outside_trace(check, "made_up_ok") is True
    assert run_outside_trace(check, "made_up_ok") is True
    assert calls == [0, 0] and kept(where) == []
    assert selfchecks()[-1] == {"gate": "made_up_ok"}

    @mosaic_gate
    def two_checks_ok() -> bool:  # one key cannot name two results
        return (run_outside_trace(check, "another_name")
                and run_outside_trace(check, "two_checks_ok")
                and run_outside_trace(check, "two_checks_ok"))

    assert two_checks_ok() is True
    (name,) = kept(where)
    assert name.startswith("tmr-gate-two_checks_ok-")
    calls.clear()
    two_checks_ok.cache_clear()
    assert mosaic_gate(two_checks_ok.__wrapped__)() is True
    assert calls == [0, 0]  # the first of its own name came from disk


def test_a_gate_asked_inside_a_gate_keeps_its_own(machine):
    where, _ = machine
    calls = []
    inner = new_process(calls)

    @mosaic_gate
    def outer_ok(n: int) -> bool:
        return inner(n + 1) and run_outside_trace(
            lambda: calls.append("outer") or True, "outer_ok")

    assert outer_ok(1) is True and calls == [2, "outer"]
    assert [n.rsplit("-", 1)[0] for n in kept(where)] == [
        "tmr-gate-made_up_ok", "tmr-gate-outer_ok"]
    assert new_process(calls)(2) is True and calls == [2, "outer"]


def test_every_mosaic_gate_of_the_package_is_wrapped():
    from tmr_tpu.ops import (
        causal_attn, flash_attn, kda, moe, pallas_attn, pallas_int8,
        pallas_nms)

    gates = [
        flash_attn.flash_window_ok, flash_attn.flash_attention_ok,
        pallas_nms.pallas_nms_compiled_ok, pallas_attn.packed_window_ok,
        pallas_attn.packed_global_ok, pallas_attn.pallas_global_ok,
        pallas_attn.pallas_fused_ok, kda.kda_chunk_ok,
        causal_attn.latent_kernel_ok, pallas_int8.pallas_int8_ok,
        moe.pairs_kernels_ok]
    for gate in gates:
        assert gate.cache_clear.__qualname__.startswith("mosaic_gate.")
        assert gate.cache_info().maxsize is None
    digest, knobs = diagnostics._source_digest(diagnostics._PACKAGE_DIR)
    assert len(digest) == 64 and "TMR_PALLAS_ATTN_BQ" in knobs


def test_what_a_check_traces_is_shared_with_no_later_trace():
    # a jaxpr JAX keeps by shape carries the stack of whoever traced it
    # first, and a Mosaic kernel's body carries it into the compile cache's
    # key: a program must read the same whether or not a check ran before it
    import jax.numpy as jnp

    traced = []

    @jax.jit
    def helper(x):
        traced.append(1)
        return x + 1

    x = jnp.ones(3)
    assert float(run_outside_trace(lambda: helper(x).sum())) == 6.0
    assert len(traced) == 1
    helper(x)
    assert len(traced) == 2  # not the check's jaxpr: traced here anew
    helper(x)
    run_outside_trace(lambda: helper(x))
    assert len(traced) == 2  # and each side keeps its own
