"""Two-process multi-controller run over one global mesh (DCN stand-in).

The reference scales across hosts with torchrun+NCCL; the TPU analog is
jax.distributed with a global mesh.  Two local processes, 4 fake CPU devices
each, run the same displaced-patch generation; both must succeed and agree
bitwise on the replicated output.
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "multihost_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow  # two worker processes, ~30 s; passes on the installed
# CPU backend (multiprocess collectives are implemented there)
def test_two_process_generation():
    port = _free_port()
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(i), "2", str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=540)
        assert p.returncode == 0, f"worker failed:\n{out}\n{err}"
        outs.append(out)
    sums = []
    for out in outs:
        for line in out.splitlines():
            if line.startswith("CHECKSUM"):
                sums.append(line.split()[2])
    assert len(sums) == 2, outs
    assert sums[0] == sums[1], f"hosts disagree: {sums}"
