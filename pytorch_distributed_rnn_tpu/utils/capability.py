"""Runtime capability probes for backend features that vary by platform.

Some tier-1 tests exercise features the ambient XLA backend may not
implement (the CPU backend cannot run multiprocess computations, and its
SPMD partitioner rejects programs that lower to a ``PartitionId``
instruction).  These are ENVIRONMENT limits, not code regressions - so
the tests probe the actual capability and ``skipif`` on the result,
keeping the suite green where the feature is honestly absent and red
where it truly broke.

Each probe runs the smallest program that exercises the capability and
caches its verdict for the process (``lru_cache``), so a suite pays each
probe once.
"""

from __future__ import annotations

import functools
import os
import subprocess
import sys

_PROBE_COORD_PORT = 12911


@functools.lru_cache(maxsize=None)
def supports_spmd_ring_collectives() -> bool:
    """Whether jitting a shard_map ring (scan over ``lax.ppermute`` with
    per-shard ``lax.axis_index`` offsets, the ``ring_flash_attention``
    shape) compiles on this backend.  XLA:CPU's SPMD partitioner rejects
    the lowered ``PartitionId`` instruction; TPU/GPU accept it."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from pytorch_distributed_rnn_tpu.ops.pallas_attention import (
        ring_flash_attention,
    )
    from pytorch_distributed_rnn_tpu.parallel import make_mesh
    from jax import shard_map

    if len(jax.devices()) < 2:
        return False
    mesh = make_mesh({"sp": 2})
    fn = shard_map(
        functools.partial(ring_flash_attention, axis="sp", causal=False),
        mesh=mesh,
        in_specs=(P(None, None, "sp"),) * 3,
        out_specs=P(None, None, "sp"),
        check_vma=False,
    )
    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 1, 16, 8)), dtype=jnp.float32)
        for _ in range(3)
    )
    try:
        jax.jit(fn)(q, k, v)
    except Exception as exc:
        if "PartitionId" in str(exc):
            return False
        raise  # an unknown failure is a regression, not a missing feature
    return True


_MULTIPROCESS_PROBE = """
import os
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=os.environ["PDRNN_PROBE_COORD"],
    num_processes=2, process_id=int(os.environ["PDRNN_PROBE_PID"]))
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
devs = jax.devices()
n = len(devs)
mesh = Mesh(np.array(devs), ("dp",))
arr = jax.make_array_from_callback(
    (n,), NamedSharding(mesh, P("dp")),
    lambda idx: np.arange(n, dtype=np.float32)[idx])
total = jax.jit(lambda x: jnp.sum(x), out_shardings=NamedSharding(mesh, P()))(arr)
assert float(total) == n * (n - 1) / 2, float(total)
print("CAP_OK")
"""


@functools.lru_cache(maxsize=None)
def supports_multiprocess_backend(timeout: float = 120.0) -> bool:
    """Whether a 2-process ``jax.distributed`` world can jit a
    computation spanning both processes' devices.  XLA:CPU raises
    "Multiprocess computations aren't implemented on the CPU backend";
    real TPU/GPU backends implement the cross-process collectives."""
    coord = f"127.0.0.1:{_PROBE_COORD_PORT}"
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        # exactly one virtual device per process: an inherited
        # device-count flag would change the probe's world shape
        flags = " ".join(
            f for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")
        )
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=1"
        ).strip()
        env["PDRNN_PROBE_COORD"] = coord
        env["PDRNN_PROBE_PID"] = str(pid)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", _MULTIPROCESS_PROBE],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True,
            )
        )
    ok = True
    try:
        for proc in procs:
            out, _ = proc.communicate(timeout=timeout)
            ok = ok and proc.returncode == 0 and "CAP_OK" in out
    except subprocess.TimeoutExpired:
        ok = False
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    return ok
