"""Test harness: force a hermetic 8-device virtual CPU mesh.

SURVEY.md §4: multi-device logic is unit-tested on a virtual CPU mesh
(`--xla_force_host_platform_device_count=8`), matching the reference's
"whole control plane in one process" test strategy.

The platform is pinned through jax.config (not only the environment), so
the suite tests the CPU mesh on a machine with a chip too; set
EDL_TEST_PLATFORM to run it elsewhere.
"""

import contextlib
import os
import sys

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

# EDL_TEST_PLATFORM overrides the hermetic-CPU pin (e.g. "tpu" on a real
# accelerator host; the meshes here want 8 devices).
_TEST_PLATFORM = (os.environ.get("EDL_TEST_PLATFORM") or "cpu").strip()
jax.config.update("jax_platforms", _TEST_PLATFORM)
# Nobody times a CPU program here (a CPU number is never a measurement), and
# the tiny programs run for milliseconds after compiling for seconds: XLA's
# backend optimisation level 0 and LLVM's expensive passes off. A constant of
# the harness, set in this process alone: the jobs' worker processes, whose
# environment is an input, compile as a deployment does.
jax.config.update("jax_disable_most_optimizations", True)


@contextlib.contextmanager
def default_pipeline():
    """XLA's own optimisations back, for the few cases the constant above
    does not suit: the AOT compiles for a described v5e, which read what
    XLA:TPU and Mosaic emit, and a case whose seconds are execution."""
    jax.config.update("jax_disable_most_optimizations", False)
    try:
        yield
    finally:
        jax.config.update("jax_disable_most_optimizations", True)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)

import pytest  # noqa: E402

#: Tests that used to be skipped on the CPU as backend-capability gaps
#: (SPMD PartitionId, multi-process worlds, tensor-parallel numerics). On
#: this installation (JAX 0.9.0) XLA:CPU runs and passes every one of them
#: (checked in PR 21), so they are tests again — in the slow tier: their
#: collectives over 8 virtual devices, or their two-process kill/resize
#: scenarios, take tens of seconds each, and on a loaded host the former can
#: hit XLA's 40 s rendezvous abort, which takes the whole pytest process
#: with it (seen once in a full tier-1 run).
heavy_on_cpu = pytest.mark.slow


def equations(jaxpr, wanted):
    """How many equations of a jaxpr `wanted(eqn)` holds for, sub-jaxprs
    (a remat's, a custom rule's, a jit's) included, kernels' bodies not."""
    n = 0
    for eqn in jaxpr.eqns:
        n += bool(wanted(eqn))
        if eqn.primitive.name == "pallas_call":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += equations(sub, wanted)
    return n


def pallas_calls(jaxpr, name):
    """How many `pallas_call`s called `name` a jaxpr holds."""
    return equations(jaxpr, lambda eqn: eqn.primitive.name == "pallas_call"
                     and eqn.params["name"] == name)


def matmuls(jaxpr, shape):
    """How many `dot_general`s of a jaxpr give a result with `shape`'s axes
    (a `dot_general` puts them in an order of its own)."""
    return equations(jaxpr, lambda eqn: eqn.primitive.name == "dot_general"
                     and sorted(eqn.outvars[0].aval.shape) == sorted(shape))


def scans_with(jaxpr, wanted):
    """The bodies of the scans of a jaxpr that `wanted(body)` holds for."""
    bodies = []
    equations(jaxpr, lambda eqn: eqn.primitive.name == "scan" and wanted(
        eqn.params["jaxpr"].jaxpr) and bodies.append(eqn.params["jaxpr"].jaxpr))
    return bodies


@pytest.fixture(scope="module")
def xla_optimises():
    """`default_pipeline()` for a module (`pytestmark = pytest.mark.
    usefixtures("xla_optimises")`): the AOT compiles' two files."""
    with default_pipeline():
        yield


@pytest.fixture(scope="session")
def mesh8():
    from elasticdl_tpu.parallel.mesh import build_mesh

    return build_mesh()


@pytest.fixture(scope="session")
def mesh_4x2():
    from elasticdl_tpu.parallel.mesh import build_mesh

    return build_mesh({"data": 4, "model": 2})


_GUARDED_ENV = ("EDL_", "JAX_", "XLA_", "TPU_")
#: What importing TensorFlow writes, once and for the whole process
#: (master/summary_service.py and training/export.py import it on first use):
#: a library's doing, the same after any test that reaches it, so no leak of
#: the test's.
_SET_BY_TENSORFLOW = {"TPU_ML_PLATFORM", "TPU_ML_PLATFORM_VERSION"}


@pytest.fixture(autouse=True)
def _environment_is_an_input():
    """Fail the test that leaves os.environ changed. Every job test starts
    workers that inherit this process's environment, so one leaked EDL_*
    variable breaks whichever tests happen to follow (PR 23: a leaked
    EDL_PROCESS_ID=2 cost the suite three 420 s waits and its time limit).
    Autouse, so set up before the test's own monkeypatch and torn down
    after it: this sees what monkeypatch put back."""
    before = {k: v for k, v in os.environ.items() if k.startswith(_GUARDED_ENV)}
    yield
    after = {k: v for k, v in os.environ.items() if k.startswith(_GUARDED_ENV)}
    leaked = sorted(
        k for k in (before.keys() | after.keys()) - _SET_BY_TENSORFLOW
        if before.get(k) != after.get(k)
    )
    if leaked:
        for k in leaked:
            os.environ.pop(k, None)
            if k in before:
                os.environ[k] = before[k]
        pytest.fail(
            "test left os.environ changed: "
            + ", ".join(f"{k}={after.get(k)!r} (was {before.get(k)!r})"
                        for k in leaked)
        )


@contextlib.contextmanager
def listening(caplog, logger_name):
    """caplog at INFO on one module's own logger: the package's logger may be
    configured `propagate=False` (`common/log_utils.py`)."""
    import logging

    log = logging.getLogger(logger_name)
    log.addHandler(caplog.handler)
    try:
        with caplog.at_level(logging.INFO, log.name):
            yield caplog
    finally:
        log.removeHandler(caplog.handler)


@pytest.fixture
def route_log(caplog):
    """What `ops/ssm.py::scan_route` logs at trace time."""
    with listening(caplog, "elasticdl_tpu.ops.ssm"):
        yield caplog
