"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

The TPU compiler is installed wherever jax[tpu] is, and it compiles for a
chip that is described and not attached: what it refuses here it would
refuse on the chip, at no chip time.  Nothing runs — these tests say
nothing about results or speed (``chip_smoke.py`` does, on the chip).

Served shapes only: bucket 512 in the ``block`` lowering, the five kernels
``peer run``'s engine dispatches to, plus the HMAC kernel sharded over a
four-device mesh (what that case guards is the sharding rule, not the
arithmetic, so it takes the cheapest kernel).

Only one process at a time may load the TPU library, and it keeps it until
it exits.  So the topology is described inside a fixture — never at
import, where every xdist worker would race for the library — and the
whole set is compiled once, by whichever worker gets here first; a worker
that is handed a later case of this file reads that worker's records from
the run's shared temp directory instead of loading the library again.
"""

import fcntl
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

BUCKET = 512  # the served bucket: `peer run --batch` default

_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|all-to-all|reduce-scatter|"
    r"collective-permute|collective-broadcast)\b"
)


def _kernels():
    """name -> (traceable kernel, input shape, dtype), single-chip cases."""
    from minbft_tpu.ops import ed25519 as ed
    from minbft_tpu.ops import p256
    from minbft_tpu.ops.hmac_sha256 import hmac_verify_kernel_packed

    return {
        "ecdsa_verify": (
            p256.ecdsa_verify_kernel_packed, (BUCKET, p256.PACKED_COLS), jnp.uint16,
        ),
        "hmac_verify": (hmac_verify_kernel_packed, (BUCKET, 24), jnp.uint32),
        "ed25519_verify": (
            ed.ed25519_verify_kernel_packed, (BUCKET, ed.PACKED_COLS), jnp.uint16,
        ),
        "ecdsa_sign": (p256.kg_comb_kernel(), (BUCKET, p256.SIGN_COLS), jnp.uint16),
        "ed25519_sign": (ed.rb_comb_kernel(), (BUCKET, ed.SIGN_COLS), jnp.uint16),
    }


SINGLE_CHIP = (
    "ecdsa_verify", "hmac_verify", "ed25519_verify", "ecdsa_sign", "ed25519_sign",
)


def _compile(fn, arg) -> dict:
    """One AOT compile -> a JSON-able record (the error, if refused)."""
    t0 = time.perf_counter()
    try:
        compiled = fn.lower(arg).compile()
    except Exception as e:  # noqa: BLE001 - the refusal IS the test result
        return {"error": f"{type(e).__name__}: {e}"[:2000]}
    mem = compiled.memory_analysis()
    return {
        "error": None,
        "seconds": round(time.perf_counter() - t0, 1),
        "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "collectives": sorted(set(_COLLECTIVE_RE.findall(compiled.as_text()))),
    }


def _compile_all() -> dict:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from minbft_tpu.ops import lowering
    from minbft_tpu.parallel import mesh as mesh_mod

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: skip, not fail
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip (the next run would warn
    # and compile again): keep the cache out of it.
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # lowering.mode() would see the CPU backend here and pick `loop`.
    lowering.set_mode("block")
    try:
        one_chip = SingleDeviceSharding(topo.devices[0])
        records = {}
        for name, (kernel, shape, dtype) in _kernels().items():
            arg = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            records[name] = _compile(jax.jit(kernel), arg)

        # The mesh-striped engine's rule (parallel/mesh.py): batch axis
        # over a 1-D mesh of the four described devices, nothing else.
        mesh = Mesh(topo.devices, (mesh_mod.BATCH_AXIS,))
        sh = NamedSharding(mesh, P(mesh_mod.BATCH_AXIS))
        arg = jax.ShapeDtypeStruct((BUCKET, 24), jnp.uint32, sharding=sh)
        sharded = jax.jit(
            jax.vmap(mesh_mod.hmac_row_verify), in_shardings=(sh,), out_shardings=sh
        )
        records["hmac_verify_sharded"] = dict(
            _compile(sharded, arg), mesh_devices=int(mesh.size)
        )
        return records
    finally:
        lowering.set_mode(None)
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def records(tmp_path_factory):
    base = tmp_path_factory.getbasetemp()
    # Under xdist each worker's basetemp is a child of the run's one.
    shared = base.parent if os.environ.get("PYTEST_XDIST_WORKER") else base
    path = shared / "tpu_compile_records.json"
    with open(shared / "tpu_compile.lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)  # released when the file closes
        if path.exists():
            return json.loads(path.read_text())
        out = _compile_all()
        path.write_text(json.dumps(out))
        return out


@pytest.mark.parametrize("name", SINGLE_CHIP)
def test_kernel_compiles_for_v5e(records, name):
    rec = records[name]
    assert rec["error"] is None, f"TPU compiler refused {name}: {rec['error']}"
    assert rec["collectives"] == []
    print(f"{name}@{BUCKET} block: compiled in {rec['seconds']}s, "
          f"code {rec['code_bytes']} B, temporaries {rec['temp_bytes']} B")


def test_sharded_hmac_compiles_without_collectives(records):
    rec = records["hmac_verify_sharded"]
    assert rec["error"] is None, f"TPU compiler refused: {rec['error']}"
    assert rec["mesh_devices"] == 4
    # The batch axis is independent lane by lane: a collective in the
    # partitioned program means the sharding rule broke.
    assert rec["collectives"] == []
