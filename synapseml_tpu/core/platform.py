"""Platform detection — which hosted environment is this process in.

Reference: ``logging/common/PlatformDetails.scala`` (Fabric via the
trident-context file, Synapse via ``AZURE_SERVICE``, Databricks via
``/dbfs``, Binder via env) and ``synapse/ml/core/platform`` on the Python
side. The TPU rebuild adds TPU-VM detection (libtpu accel devices / the
``TPU_NAME`` metadata env GKE and GCE TPU VMs export) since executor↔TPU-host
pinning decisions key off it.

``env``/``root`` are injectable so detection is unit-testable off-platform.

The second half decides what a PROCESS does with the accelerator it finds:
where its compile cache lives (:func:`enable_compile_cache`), whether a
Pallas kernel compiles or interprets (:func:`pallas_interpret`), and whether
a launcher may start worker processes that would each claim the chip
(:func:`check_chip_launch`). None of it runs at ``import synapseml_tpu``.
"""

from __future__ import annotations

import os
import sys

__all__ = [
    "PLATFORM_FABRIC", "PLATFORM_SYNAPSE", "PLATFORM_DATABRICKS",
    "PLATFORM_BINDER", "PLATFORM_TPU_VM", "PLATFORM_UNKNOWN",
    "current_platform", "running_on_fabric", "running_on_synapse",
    "running_on_databricks", "running_on_tpu_vm",
    "enable_compile_cache", "pallas_interpret", "check_chip_launch",
]

# names mirror PlatformDetails.scala (Fabric reports as synapse_internal)
PLATFORM_FABRIC = "synapse_internal"
PLATFORM_SYNAPSE = "synapse"
PLATFORM_DATABRICKS = "databricks"
PLATFORM_BINDER = "binder"
PLATFORM_TPU_VM = "tpu_vm"
PLATFORM_UNKNOWN = "unknown"

SYNAPSE_PROJECT_NAME = "Microsoft.ProjectArcadia"
TRIDENT_CONTEXT_PATH = "home/trusted-service-user/.trident-context"


def current_platform(env: dict | None = None, root: str = "/") -> str:
    """Detection precedence mirrors the reference: the trident-context file
    is authoritative for Fabric; ``AZURE_SERVICE`` marks Synapse; ``/dbfs``
    Databricks; Binder its launch-host env; then TPU-VM markers."""
    e = os.environ if env is None else env
    if os.path.exists(os.path.join(root, TRIDENT_CONTEXT_PATH)):
        return PLATFORM_FABRIC
    if e.get("AZURE_SERVICE") == SYNAPSE_PROJECT_NAME:
        return PLATFORM_SYNAPSE
    if os.path.exists(os.path.join(root, "dbfs")):
        return PLATFORM_DATABRICKS
    if "BINDER_LAUNCH_HOST" in e:
        return PLATFORM_BINDER
    if "TPU_NAME" in e or "TPU_WORKER_ID" in e \
            or os.path.exists(os.path.join(root, "dev", "accel0")):
        return PLATFORM_TPU_VM
    return PLATFORM_UNKNOWN


def running_on_fabric(env: dict | None = None, root: str = "/") -> bool:
    return current_platform(env, root) == PLATFORM_FABRIC


def running_on_synapse(env: dict | None = None, root: str = "/") -> bool:
    return current_platform(env, root) == PLATFORM_SYNAPSE


def running_on_databricks(env: dict | None = None, root: str = "/") -> bool:
    return current_platform(env, root) == PLATFORM_DATABRICKS


def running_on_tpu_vm(env: dict | None = None, root: str = "/") -> bool:
    return current_platform(env, root) == PLATFORM_TPU_VM


# ---------------------------------------------------------------------------
# what a process does with the accelerator it finds
# ---------------------------------------------------------------------------

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Give this process JAX's persistent compilation cache; returns the
    directory. Called by every process entry point the repo owns, never at
    import. ``JAX_COMPILATION_CACHE_DIR`` wins and is left to jax (which
    reads the variable itself); otherwise the cache is the FIXED path
    ``<checkout>/.jax_cache`` — the directory is part of the cache key, so
    a temp/pid/timestamp path would never hit. Spawned workers derive the
    same path from the same package location."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def pallas_interpret() -> bool:
    """The ONE decision of ``pallas_call(interpret=...)``: compiled (Mosaic)
    on ``tpu``, interpreted on ``cpu`` — the tests' only way to run a kernel
    — and an error on any other backend: a backend that is not literally
    ``tpu`` must not get the interpreter under a kernel's name."""
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels run compiled on 'tpu' and interpreted on 'cpu'; "
        f"the default backend is {backend!r} — pick the XLA path "
        f"(attn_impl='einsum', histogram_impl='segment') on this backend")


def _visible_tpu_chips() -> int:
    """TPU chips on this host's PCI bus, counted WITHOUT initialising a JAX
    backend (which would claim them). An upper bound: a machine may hand a
    process fewer (the one-chip v5e machines show all four)."""
    from jax._src import hardware_utils

    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def _parent_holds_tpu() -> bool:
    if "jax" not in sys.modules:
        return False
    import jax
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized() \
        and jax.default_backend() == "tpu"


def check_chip_launch(n_processes: int, env) -> None:
    """Refuse a worker launch that can only hang or fail on a chip host.

    ``env`` is the environment the children will get. A chip belongs to one
    process at a time, and a process takes every chip it can see, so
    children that would run on the TPU (``JAX_PLATFORMS`` unset or naming
    ``tpu``, on a host with chips) are refused when the launching process
    already holds the TPU backend or when more than one of them is asked
    for. ``JAX_PLATFORMS=cpu`` in ``env`` is the explicit way to run CPU
    workers; nothing is defaulted to it."""
    platforms = [p.strip() for p in
                 (env.get("JAX_PLATFORMS") or "").lower().split(",") if p.strip()]
    if platforms and "tpu" not in platforms:
        return
    if _parent_holds_tpu():
        raise RuntimeError(
            "this process has initialised the TPU backend and holds the "
            f"chip; {n_processes} worker process(es) launched from it would "
            "fail or hang waiting for it. Launch from a parent that has not "
            "touched JAX, or pass JAX_PLATFORMS=cpu in the workers' "
            "environment to run them on the CPU explicitly")
    chips = _visible_tpu_chips()
    if chips and n_processes > 1:
        raise RuntimeError(
            f"{n_processes} chip-holding worker processes asked for on a "
            f"host with {chips} TPU chip(s): every worker claims all chips "
            "it can see, so only one can start. Run one worker (one process "
            "can drive every chip), or pass JAX_PLATFORMS=cpu in the "
            "workers' environment to run them on the CPU explicitly")
