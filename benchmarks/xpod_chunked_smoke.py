"""Cross-pod chunked transfer smoke: the planned mesh path on a CPU mesh.

Exercises the acceptance property of the plan/execute API: a
``TransferPlan`` executed on a multi-pod mesh with ``n_chunks > 1`` (per-
chunk ``lax.ppermute``, double-buffered inside ``shard_map``) is
bit-identical to the whole-tensor collective, and the per-chunk
collectives move the same compressed payload (HLO collective-permute bytes
are compared).

CI runs this with ``SPLITZIP_BENCH_SMOKE=1`` (tiny cache) as
``python -m benchmarks.run --only xpod_chunked`` — its own process, so the
host-device override below takes effect before jax initializes.  In a full
benchmark sweep where jax already initialized with < 8 devices, the module
reports a skip instead of failing.
"""

from __future__ import annotations

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()

import jax                                                        # noqa: E402
import jax.numpy as jnp                                           # noqa: E402
import numpy as np                                                # noqa: E402

SMOKE = bool(int(os.environ.get("SPLITZIP_BENCH_SMOKE", "0")))


def run(emit) -> None:
    if jax.device_count() < 8:
        emit("xpod_chunked", "skipped",
             dict(reason=f"needs 8 host devices, have {jax.device_count()} "
                         "(run as its own process)"))
        return

    from repro.analysis.roofline import collective_bytes_from_hlo
    from repro.core import codebook as cbm
    from repro.launch.mesh import make_mesh
    from repro.serving.plan import TransferConfig, TransferPlan

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    rng = np.random.default_rng(0)
    seq = 64 if SMOKE else 256

    def kv_like(shape):
        x = rng.normal(size=shape) * rng.choice([0.25, 1.0, 4.0], size=shape)
        return jnp.asarray(x, dtype=jnp.bfloat16)

    cache = {"k": kv_like((2, 4, seq, 2, 16)), "v": kv_like((2, 4, seq, 2, 16)),
             "ssm": jnp.asarray(rng.normal(size=(2, 4, 32)), jnp.float32)}
    cb = cbm.calibrate(
        [np.asarray(jax.lax.bitcast_convert_type(cache["k"], jnp.uint16))],
        k=16)

    def run_one(n_chunks):
        tc = TransferConfig(codebook=cb, chunk=256, cap=16, n_chunks=n_chunks,
                            compress_fp32=True)
        sess = TransferPlan.build(cache, tc, mesh=mesh).session()
        out = sess.transfer(cache)
        colls = collective_bytes_from_hlo(sess.lower_hlo(cache))
        return out, colls["collective-permute"]

    whole, whole_bytes = run_one(1)
    piped, piped_bytes = run_one(4)

    def bits(t):
        return [np.asarray(jax.lax.bitcast_convert_type(
            x, jnp.uint16 if x.dtype.itemsize == 2 else jnp.uint32))
            for x in jax.tree.leaves(t)]

    exact_in = all(np.array_equal(a, b) for a, b in zip(bits(cache), bits(piped)))
    exact_whole = all(np.array_equal(a, b)
                      for a, b in zip(bits(whole), bits(piped)))
    assert exact_in, "chunked mesh transfer must be bit-exact vs input"
    assert exact_whole, "chunked mesh transfer must match whole-tensor path"

    emit("xpod_chunked", "parity", dict(
        bit_exact_vs_input=exact_in, bit_exact_vs_whole_tensor=exact_whole,
        whole_permute_bytes=int(whole_bytes),
        chunked_permute_bytes=int(piped_bytes),
        n_chunks=4, mesh="pod2,data2,model2"))
