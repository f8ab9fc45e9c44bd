"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip sharding tests run on the host platform with 8 virtual devices
(the TPU-world equivalent of the reference's `examples/n-workers.sh`
localhost-cluster harness — see SURVEY.md §4). Must be set before jax import.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402  (after the env setup above, by design)
import pytest  # noqa: E402

# A pytest plugin (jaxtyping) imports jax before this conftest runs, so the
# env vars above may be too late — force the platform via config too.
jax.config.update("jax_platforms", "cpu")

# f32 matmuls must really be f32 for oracle-equivalence tests (this JAX
# build's default matmul precision is reduced even on CPU).
jax.config.update("jax_default_matmul_precision", "highest")


# -- one spelling a serving knob (PR 45; helpers.FORMER_TWINS) -------------------
#
# A module that tests knobs names their former DLLAMA_* twins in `KNOB_TWINS`
# and takes these two servers, each built once a module as
# `python -m dllama_tpu.runtime.api_server` builds it.


@pytest.fixture(scope="module")
def unflagged(tmp_path_factory):
    """None of the nineteen flags passed, all nineteen former variables set."""
    from helpers import flags_state

    yield from flags_state(tmp_path_factory, former_twins=True)


@pytest.fixture(scope="module")
def flagged(request, tmp_path_factory):
    """Each flag of the module's `KNOB_TWINS` passed, no variable set."""
    from helpers import flags_state, twin_flags

    yield from flags_state(tmp_path_factory, *twin_flags(*request.module.KNOB_TWINS))
