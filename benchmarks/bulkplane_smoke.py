"""Bulk-data plane smoke: checkpoint save/restore + 2-pod ring_reduce.

Exercises the two bulk-plane executors outside the KV hop:

* **persistent** — a train-state pytree (bf16 params, fp32 optimizer
  moments, int step) round-trips through ``checkpoint.save_tree`` /
  ``load_tree`` bit-exactly via ``distributed/checkpoint.Checkpointer``, and
  a corrupted frame falls back to the previous step (the fallback is driven
  by Fletcher-32 + ``WireIntegrityError``, not ad-hoc hashing).
* **collective** — ``compressed_cross_pod_mean`` runs the compressed ring
  of ``training/grad_compress.py`` on a 2-pod CPU mesh and matches the
  ``jnp.mean`` all-reduce bitwise, with plan-derived wire accounting.

CI runs this with ``SPLITZIP_BENCH_SMOKE=1`` as
``python -m benchmarks.run --only bulkplane`` — its own process, so the
host-device override below takes effect before jax initializes.
"""

from __future__ import annotations

import os
import shutil
import tempfile

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8").strip()

import jax                                                        # noqa: E402
import jax.numpy as jnp                                           # noqa: E402
import numpy as np                                                # noqa: E402

SMOKE = bool(int(os.environ.get("SPLITZIP_BENCH_SMOKE", "0")))


def run(emit) -> None:
    from repro.distributed import checkpoint as CKPT
    from repro.launch.mesh import make_mesh
    from repro.training import grad_compress as GC

    rng = np.random.default_rng(0)
    dim = 128 if SMOKE else 512

    # -- persistent executor: checkpoint round-trip + corruption fallback ----
    state = {"params": {"w": jnp.asarray(rng.normal(size=(dim, dim)),
                                         jnp.bfloat16)},
             "opt": {"m": jnp.asarray(rng.normal(size=(dim, dim)),
                                      jnp.float32)},
             "step": jnp.asarray(1, jnp.int32)}
    d = tempfile.mkdtemp(prefix="bulkplane_")
    try:
        ck = CKPT.Checkpointer(d)
        ck.save(1, state, extra={"arch": "bench"})
        ck.save(2, state)
        tree, _, step = ck.restore(state)
        rt_exact = step == 2 and all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(state)))
        target = os.path.join(d, "step_0000000002")
        fname = max((f for f in os.listdir(target) if f.endswith(".szc")),
                    key=lambda f: os.path.getsize(os.path.join(target, f)))
        blob = bytearray(open(os.path.join(target, fname), "rb").read())
        blob[len(blob) // 2] ^= 0x55
        open(os.path.join(target, fname), "wb").write(bytes(blob))
        tree, _, step = ck.restore(state)
        fb_exact = step == 1 and all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(state)))
        assert rt_exact, "checkpoint round-trip must be bit-exact"
        assert fb_exact, "corrupted step must fall back bit-exactly"
        emit("bulkplane", "checkpoint", dict(
            roundtrip_bit_exact=rt_exact, fallback_bit_exact=fb_exact,
            verify_failures=int(ck.stats.verify_failures),
            wire_bytes=int(ck.stats.wire_bytes)))
    finally:
        shutil.rmtree(d, ignore_errors=True)

    # -- collective executor: 2-pod compressed ring all-reduce ---------------
    if jax.device_count() < 2:
        emit("bulkplane", "ring_skipped",
             dict(reason=f"needs 2 host devices, have {jax.device_count()}"))
        return
    mesh = make_mesh((2,), ("pod",))
    # small-integer bf16: fp32 ring sums are exact in any hop order
    grads = {"w": jnp.asarray(rng.integers(-8, 8, size=(2, dim, dim)),
                              jnp.bfloat16),
             "b": jnp.asarray(rng.integers(-8, 8, size=(2, dim)),
                              jnp.bfloat16)}
    cb = GC.calibrate_on_grads(jax.tree.map(lambda g: g[0], grads))
    ref = jax.tree.map(lambda g: jnp.mean(g.astype(jnp.float32), axis=0)
                       .astype(g.dtype), grads)
    out = GC.compressed_cross_pod_mean(grads, mesh, codebook=cb)
    ring_exact = all(np.asarray(out[k]).tobytes() == np.asarray(ref[k]).tobytes()
                     for k in ref)
    assert ring_exact, "ring_reduce must match jnp.mean bitwise"
    s = GC.last_stats
    emit("bulkplane", "ring_reduce", dict(
        bit_exact_vs_mean=ring_exact, n_pod=2,
        wire_bytes=int(s.wire_bytes),
        raw_ring_fallbacks=int(s.raw_refetches),
        analytic_wire_bytes=int(GC.cross_pod_wire_bytes(
            jax.tree.map(lambda g: g[0], grads), n_pod=2))))
