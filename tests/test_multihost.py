"""Multi-host (DCN) bootstrap test: two real OS processes join through
`initialize_multihost` (the SPMD replacement for the reference's
root/worker TCP handshake, src/nn/nn-network.cpp:295-379) and run a
cross-process psum over a global mesh — the collective rides the
distributed runtime's data plane (Gloo on CPU; ICI/DCN on TPU pods),
exactly the path a v5e-16+ pod launch takes."""

import os
import socket
import subprocess
import sys

import pytest

from helpers import REPO_ROOT

# heavyweight end-to-end surface: run with the full suite / CI;
# deselect via -m 'not slow' for the fast local loop
pytestmark = pytest.mark.slow

_WORKER = r"""
import sys
sys.path.insert(0, sys.argv[3])
import jax
jax.config.update("jax_platforms", "cpu")
pid = int(sys.argv[1])
from dllama_tpu.parallel.mesh import initialize_multihost
initialize_multihost(
    coordinator_address=f"127.0.0.1:{sys.argv[2]}", num_processes=2,
    process_id=pid,
)
import numpy as np
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 2, jax.device_count()

mesh = Mesh(np.asarray(jax.devices()).reshape(2), ("tp",))
# each process contributes its own shard (value = pid + 1); the psum must
# see both shards -> 3.0 everywhere
garr = jax.make_array_from_single_device_arrays(
    (16,), NamedSharding(mesh, P("tp")),
    [jax.device_put(np.full(8, pid + 1.0, np.float32),
                    jax.local_devices()[0])],
)
out = jax.jit(
    shard_map(lambda a: jax.lax.psum(a, "tp"), mesh=mesh,
              in_specs=P("tp"), out_specs=P("tp"), check_vma=False)
)(garr)
local = np.asarray(out.addressable_shards[0].data)
assert np.allclose(local, 3.0), local
print(f"proc {pid} psum ok", flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_two_workers(tmp_path, worker_src, marker, extra_argv=(), timeout=300):
    """Launch the worker script as 2 coordinated processes and assert both
    exit 0 printing `marker`. On a per-process timeout, kills the stragglers
    and surfaces the output of EVERY process that already finished (a fast
    assert in one worker otherwise hangs its peer in a collective, and the
    bare TimeoutExpired would hide the root cause)."""
    port = _free_port()
    script = tmp_path / "mh_worker.py"
    script.write_text(worker_src)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        # one local device per process (the conftest's 8-device flag would
        # otherwise leak in and give 16 global devices)
        XLA_FLAGS="--xla_force_host_platform_device_count=1",
    )
    # the coordinator (process 0) must be up before/while 1 dials in;
    # launch both and join
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(pid), str(port), REPO_ROOT,
             *extra_argv],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        )
        for pid in (0, 1)
    ]
    outs = []
    timed_out = None
    for pid, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
        except subprocess.TimeoutExpired:
            timed_out = pid
            for q in procs:
                q.kill()
            out, _ = p.communicate()
            outs.append(out)
    if timed_out is not None:
        raise AssertionError(
            f"proc {timed_out} timed out after {timeout}s; collected "
            "outputs:\n"
            + "\n".join(f"--- proc {i} ---\n{o}" for i, o in enumerate(outs))
        )
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out}"
        assert marker in out, out


def _single_process_expected(n_steps=6, prompt=(1, 2, 3, 4, 5), fwd=None):
    """Greedy single-process token stream on the synthetic tiny model —
    the oracle every cross-process worker must reproduce."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dllama_tpu.models import forward, init_kv_cache
    from dllama_tpu.models.synthetic import make_header, random_params

    h = make_header("tiny")
    params = random_params(h, dtype=jnp.float32, seed=3)
    cache = init_kv_cache(h, 1)
    prompt = list(prompt)

    @jax.jit
    def step(params, tokens, cache, pos):
        logits, cache = forward(params, h, tokens, pos, cache)
        return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), cache

    _, cache = step(
        params, jnp.asarray([prompt[:-1]], jnp.int32), cache, jnp.int32(0)
    )
    pos, tok, expected = len(prompt) - 1, prompt[-1], []
    for _ in range(n_steps):
        nxt, cache = step(
            params, jnp.asarray([[tok]], jnp.int32), cache, jnp.int32(pos)
        )
        tok = int(np.asarray(nxt)[0])
        pos += 1
        expected.append(tok)
    return expected


def test_two_process_multihost_psum(tmp_path):
    _run_two_workers(tmp_path, _WORKER, "psum ok", timeout=180)


# Full cross-process INFERENCE: the reference's worker path runs the whole
# model over the wire (src/app.cpp:306-365, nn-network.cpp:295-379); the
# SPMD analogue is a tp=2 global mesh spanning two OS processes, sharded
# params/KV cache built per-process from the same seed, and greedy decode
# whose all-reduces cross the process boundary on every layer. Token-exact
# parity with a single-process run is asserted in the parent.
_INFER_WORKER = r"""
import sys
sys.path.insert(0, sys.argv[3])
import jax
jax.config.update("jax_platforms", "cpu")
pid = int(sys.argv[1])
expected = [int(t) for t in sys.argv[4].split(",")]
from dllama_tpu.parallel.mesh import initialize_multihost, make_mesh
initialize_multihost(
    coordinator_address=f"127.0.0.1:{sys.argv[2]}", num_processes=2,
    process_id=pid,
)
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from dllama_tpu.models import forward, init_kv_cache
from dllama_tpu.models.synthetic import make_header, random_params
from dllama_tpu.parallel.sharding import cache_specs

assert jax.process_count() == 2 and jax.device_count() == 2
mesh = make_mesh(tp=2)
h = make_header("tiny")
# same seed on both processes -> identical global params, tp-sharded
params = random_params(h, dtype=jnp.float32, seed=3, mesh=mesh)
rep = NamedSharding(mesh, P())
cache_sh = {k: NamedSharding(mesh, v) for k, v in cache_specs(h).items()}
cache = jax.jit(
    lambda: init_kv_cache(h, 1), out_shardings=cache_sh
)()

def _fwd(params, tokens, cache, pos):
    logits, cache = forward(params, h, tokens, pos, cache, mesh=mesh)
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), cache

step = jax.jit(_fwd, out_shardings=(rep, cache_sh))

def put_tokens(rows):
    arr = np.asarray(rows, np.int32)
    return jax.make_array_from_callback(arr.shape, rep, lambda idx: arr[idx])

prompt = [1, 2, 3, 4, 5]
_, cache = step(params, put_tokens([prompt[:-1]]), cache, jnp.int32(0))
pos, tok, outs = len(prompt) - 1, prompt[-1], []
for _ in range(len(expected)):
    nxt, cache = step(params, put_tokens([[tok]]), cache, jnp.int32(pos))
    tok = int(np.asarray(nxt.addressable_shards[0].data)[0])
    pos += 1
    outs.append(tok)
assert outs == expected, f"proc {pid}: {outs} != {expected}"
print(f"proc {pid} inference ok", flush=True)
"""


def test_two_process_inference_token_parity(tmp_path):
    """Prefill + 6 greedy decode steps on a tp=2 mesh spanning two OS
    processes must reproduce the single-process tokens exactly."""
    expected = _single_process_expected()
    _run_two_workers(
        tmp_path, _INFER_WORKER, "inference ok",
        extra_argv=[",".join(str(t) for t in expected)],
    )


# Pipeline stages SPANNING PROCESSES: the reference's cluster story is TP
# workers over TCP, capped at nNodes <= nKvHeads (src/app.cpp:236-240);
# pipeline stages have no such cap and their ppermute hand-offs are the
# smallest cross-node payload in the model — this pins that the pp
# schedule's collectives (activation ring + exit psum) really run over
# the distributed data plane (Gloo here; DCN on a pod), token-exact.
_PP_WORKER = r"""
import sys
sys.path.insert(0, sys.argv[3])
import jax
jax.config.update("jax_platforms", "cpu")
pid = int(sys.argv[1])
expected = [int(t) for t in sys.argv[4].split(",")]
from dllama_tpu.parallel.mesh import initialize_multihost, make_mesh
initialize_multihost(
    coordinator_address=f"127.0.0.1:{sys.argv[2]}", num_processes=2,
    process_id=pid,
)
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from dllama_tpu.models import init_kv_cache
from dllama_tpu.models.synthetic import make_header, random_params
from dllama_tpu.parallel.pipeline import forward_pp
from dllama_tpu.parallel.sharding import cache_specs

assert jax.process_count() == 2 and jax.device_count() == 2
mesh = make_mesh(pp=2)
h = make_header("tiny")
params = random_params(h, dtype=jnp.float32, seed=3, mesh=mesh)
rep = NamedSharding(mesh, P())
cache_sh = {
    k: NamedSharding(mesh, v) for k, v in cache_specs(h, pp=True).items()
}
cache = jax.jit(
    lambda: init_kv_cache(h, 1), out_shardings=cache_sh
)()

def _fwd(params, tokens, cache, pos):
    logits, cache = forward_pp(params, h, tokens, pos, cache, mesh)
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), cache

step = jax.jit(_fwd, out_shardings=(rep, cache_sh))

def put_tokens(rows):
    arr = np.asarray(rows, np.int32)
    return jax.make_array_from_callback(arr.shape, rep, lambda idx: arr[idx])

prompt = [1, 2, 3, 4, 5]
_, cache = step(params, put_tokens([prompt[:-1]]), cache, jnp.int32(0))
pos, tok, outs = len(prompt) - 1, prompt[-1], []
for _ in range(len(expected)):
    nxt, cache = step(params, put_tokens([[tok]]), cache, jnp.int32(pos))
    tok = int(np.asarray(nxt.addressable_shards[0].data)[0])
    pos += 1
    outs.append(tok)
assert outs == expected, f"proc {pid}: {outs} != {expected}"
print(f"proc {pid} pp inference ok", flush=True)
"""


def test_two_process_pipeline_token_parity(tmp_path):
    """Greedy decode over pp=2 stages living in DIFFERENT OS processes
    must reproduce the single-process tokens exactly (stage hand-offs +
    exit psum over the distributed data plane)."""
    expected = _single_process_expected()
    _run_two_workers(
        tmp_path, _PP_WORKER, "pp inference ok",
        extra_argv=[",".join(str(t) for t in expected)],
    )
