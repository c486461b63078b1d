"""The bulk-data plane: checkpoint, elastic resharding, resilient training,
and gradient compression all ride TransferPlan — the checkpoint module's
persistent executor (save_tree/load_tree: SZ02 frames + manifest), the
gradient module's compressed ring all-reduce, the reshard hop through a
TransferSession, and the consumer seams: corrupt-frame fallback is bit-exact, ring gradients
match jnp.mean bitwise, reshard round-trips a train state, and recovery
surfaces non-zero TransferStats.refetches under injected faults."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig
from repro.configs.smollm_135m import CONFIG as SMOLLM
from repro.core import codebook as cbm
from repro.core.profile import PAPER_RATIO
from repro.core.wire import WireIntegrityError
from repro.distributed import checkpoint as CKPT
from repro.distributed import elastic as EL
from repro.distributed.fault_tolerance import FaultConfig, ResilientTrainer
from repro.serving.faults import FaultPlan
from repro.serving.plan import TransferConfig, TransferPlan, TransferStats
from repro.training import grad_compress as GC


def _train_state(seed=0):
    """bf16 params + fp32 optimizer moments + int step: all three persistent
    routes in one pytree."""
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": jnp.asarray(rng.normal(size=(96, 64)), jnp.bfloat16),
                   "tiny": jnp.asarray(rng.normal(size=(4,)), jnp.bfloat16)},
        "opt": {"m": jnp.asarray(rng.normal(size=(96, 64)), jnp.float32)},
        "step": jnp.asarray(11, jnp.int32),
    }


def _assert_bit_identical(a_tree, b_tree):
    for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        assert a.dtype == b.dtype and a.shape == b.shape


def _subprocess_env():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get(
        "PYTHONPATH", "")
    return env


# ---------------------------------------------------------------------------
# persistent executor: checkpoint.save_tree / checkpoint.load_tree
# ---------------------------------------------------------------------------

class TestPersistentExecutor:
    def test_roundtrip_all_routes_bit_exact(self, tmp_path):
        state = _train_state()
        tc = TransferConfig(codebook=CKPT.CKPT_CODEBOOK, backend="wire",
                            compress_fp32=True, min_compress_elems=64)
        plan = TransferPlan.build(state, tc)
        CKPT.save_tree(str(tmp_path / "ck"), state, plan, extra={"note": "x"})
        tree, extra, s = CKPT.load_tree(str(tmp_path / "ck"), plan)
        _assert_bit_identical(tree, state)
        assert extra == {"note": "x"}
        # routes: w -> splitzip stream, m -> fp32 hi/lo, tiny -> raw (below
        # min_compress_elems), step -> raw
        assert s.leaf_ok.get("params/w") is True
        assert s.fp32_lo_wire_bytes > 0
        assert s.raw_passthrough_bytes > 0

    def test_min_compress_elems_routes_small_leaves_raw(self):
        state = _train_state()
        tc = TransferConfig(codebook=CKPT.CKPT_CODEBOOK, backend="wire",
                            min_compress_elems=64)
        plan = TransferPlan.build(state, tc)
        routes = {r.key: r.route for r in plan.routes}
        assert routes["params/tiny"] == "raw"      # 4 elems < 64
        assert routes["params/w"] == "splitzip"

    def test_corrupt_frame_raises_and_publishes_stats(self, tmp_path):
        state = _train_state()
        tc = TransferConfig(codebook=CKPT.CKPT_CODEBOOK, backend="wire",
                            compress_fp32=True)
        plan = TransferPlan.build(state, tc)
        path = str(tmp_path / "ck")
        CKPT.save_tree(path, state, plan)
        fname = max((f for f in os.listdir(path) if f.endswith(".szc")),
                    key=lambda f: os.path.getsize(os.path.join(path, f)))
        fpath = os.path.join(path, fname)
        blob = bytearray(open(fpath, "rb").read())
        blob[len(blob) // 2] ^= 0x55
        open(fpath, "wb").write(bytes(blob))
        stats = TransferStats(chunk_wire_bytes=[], chunk_ok=[],
                              raw_passthrough_bytes=0.0, n_elements=0)
        with pytest.raises(WireIntegrityError):
            CKPT.load_tree(path, plan, stats=stats)
        # the abandoned load still accounts: the fallback policy upstream
        # (Checkpointer.restore) aggregates these
        assert stats.verify_failures > 0
        assert False in stats.leaf_ok.values()

    def test_injected_wire_faults_heal_via_refetch(self, tmp_path):
        state = _train_state()
        tc = TransferConfig(codebook=CKPT.CKPT_CODEBOOK, backend="wire",
                            compress_fp32=True)
        plan = TransferPlan.build(state, tc)
        CKPT.save_tree(str(tmp_path / "ck"), state, plan)
        tree, _, s = CKPT.load_tree(
            str(tmp_path / "ck"), plan,
            faults=FaultPlan(corrupt_chunks=(0,), persistent_attempts=1))
        _assert_bit_identical(tree, state)
        assert s.refetches > 0
        assert s.faults_injected > 0


# ---------------------------------------------------------------------------
# checkpoint seam: corrupt one frame -> falls back to previous step bit-exactly
# ---------------------------------------------------------------------------

class TestCheckpointFallback:
    def test_corrupt_checkpoint_falls_back_bit_exactly(self, tmp_path):
        d = str(tmp_path)
        good, bad = _train_state(seed=1), _train_state(seed=2)
        ck = CKPT.Checkpointer(d)
        ck.save(10, good, extra={"arch": "a"})
        ck.save(20, bad)
        target = os.path.join(d, "step_0000000020")
        fname = max((f for f in os.listdir(target) if f.endswith(".szc")),
                    key=lambda f: os.path.getsize(os.path.join(target, f)))
        fpath = os.path.join(target, fname)
        blob = bytearray(open(fpath, "rb").read())
        blob[len(blob) // 2] ^= 0xFF
        open(fpath, "wb").write(bytes(blob))
        tree, extra, step = ck.restore(good)
        assert step == 10 and extra == {"arch": "a"}
        _assert_bit_identical(tree, good)
        # the abandoned candidate's verify failures surface on the manager
        assert ck.stats.verify_failures > 0

    def test_all_candidates_corrupt_raises(self, tmp_path):
        d = str(tmp_path)
        state = _train_state()
        ck = CKPT.Checkpointer(d)
        ck.save(5, state)
        target = os.path.join(d, "step_0000000005")
        for f in os.listdir(target):
            if f.endswith(".szc"):
                open(os.path.join(target, f), "wb").write(b"junk")
        with pytest.raises(CKPT.CheckpointCorrupt):
            ck.restore(state)

    def test_module_level_api_roundtrip(self, tmp_path):
        d = str(tmp_path)
        state = _train_state(seed=3)
        CKPT.save(d, 1, state)
        CKPT.save(d, 2, state, extra={"k": 1})
        assert CKPT.steps_available(d) == [1, 2]
        assert CKPT.latest_step(d) == 2
        assert CKPT.checkpoint_bytes(d, 2) > 0
        tree, extra, step = CKPT.restore(d, state)
        assert step == 2 and extra == {"k": 1}
        _assert_bit_identical(tree, state)


# ---------------------------------------------------------------------------
# resilient-training seam: recovery is verified AND accounted
# ---------------------------------------------------------------------------

class TestResilientTrainerStats:
    def test_recovery_surfaces_refetches_under_faultplan(self, tmp_path):
        ck = CKPT.Checkpointer(
            str(tmp_path),
            faults=FaultPlan(corrupt_chunks=(0,), persistent_attempts=1))

        def step_fn(state, step):
            return jax.tree.map(lambda x: x + 1, state), {"loss": float(step)}

        fired = set()

        def faults(step):
            if step in {7, 12} and step not in fired:
                fired.add(step)
                return "crash"
            return None

        tr = ResilientTrainer(
            step_fn, cfg=FaultConfig(max_restarts=4, checkpoint_every=5),
            fault_source=faults, checkpointer=ck)
        rep = tr.run({"w": jnp.zeros((64, 64), jnp.bfloat16)}, 20)
        assert rep.steps_completed == 20 and rep.restarts == 2
        assert rep.transfer_stats is not None
        assert rep.transfer_stats.refetches > 0
        assert rep.transfer_stats.verify_failures > 0
        assert rep.transfer_stats.wire_bytes > 0

    def test_closure_api_unchanged(self):
        saves = []
        state0 = {"w": 0}

        def step_fn(state, step):
            return state, {"loss": 0.0}

        tr = ResilientTrainer(step_fn, lambda s, st: saves.append(s),
                              lambda: (state0, 0),
                              FaultConfig(max_restarts=4, checkpoint_every=5))
        rep = tr.run(state0, 6)
        assert rep.steps_completed == 6
        assert rep.transfer_stats is None
        assert saves == [5, 6]


# ---------------------------------------------------------------------------
# elastic seam: legal_meshes divisibility + reshard round-trip
# ---------------------------------------------------------------------------

class TestLegalMeshes:
    def test_rejects_dp_exceeding_global_batch(self):
        """Regression: global_batch=4 on 8 chips must not admit dp=8 (zero
        per-replica batch).  Every surviving mesh has a non-empty, equal
        per-replica slice."""
        shape = ShapeConfig(name="t", seq_len=128, global_batch=4,
                            kind="train")
        plans = EL.legal_meshes(8, SMOLLM, shape)
        assert plans, "some legal mesh must survive (model-parallel splits)"
        for p in plans:
            dp = p.shape[0]
            assert shape.global_batch % dp == 0
            assert dp <= shape.global_batch
        assert (8, 1) not in {p.shape for p in plans}

    def test_multi_pod_divisibility(self):
        shape = ShapeConfig(name="t", seq_len=128, global_batch=4,
                            kind="train")
        for p in EL.legal_meshes(8, SMOLLM, shape, multi_pod=True, n_pods=2):
            dp = p.shape[0] * p.shape[1]       # pod * data
            assert shape.global_batch % dp == 0


RESHARD_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp, numpy as np
from repro.distributed import elastic as EL

rng = np.random.default_rng(3)
state = {"params": {"w": jnp.asarray(rng.normal(size=(256, 64)), jnp.bfloat16)},
         "opt": {"m": jnp.asarray(rng.normal(size=(256, 64)), jnp.float32)},
         "step": jnp.asarray(7, jnp.int32)}
old = EL.MeshPlan((4, 2), ("data", "model"), 0.0)
new = EL.MeshPlan((2, 2), ("data", "model"), 0.0)
out, stats = EL.reshard(state, old, new)
assert stats.wire_bytes > 0 and all(stats.leaf_ok.values())
back, _ = EL.reshard(out, new, old)
for t in (out, back):
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(state)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
mesh_axes = dict(jax.tree.leaves(out)[0].sharding.mesh.shape)
assert mesh_axes == {"data": 2, "model": 2}, mesh_axes
print("RESHARD-OK")
"""


class TestReshard:
    def test_round_trip_across_mesh_plans_subprocess(self):
        """Acceptance: a train state ships (4,2) -> (2,2) -> (4,2) through
        the bulk-data plane bit-exactly, landing on the new mesh.  Own
        process: the device-count override must precede jax init."""
        out = subprocess.run([sys.executable, "-c", RESHARD_SCRIPT],
                             capture_output=True, text=True,
                             env=_subprocess_env(), timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "RESHARD-OK" in out.stdout

    def test_rejects_oversized_mesh(self):
        state = {"w": jnp.zeros((8,), jnp.bfloat16)}
        big = EL.MeshPlan((64, 64), ("data", "model"), 0.0)
        with pytest.raises(ValueError, match="devices"):
            EL.reshard(state, None, big)


# ---------------------------------------------------------------------------
# gradient seam: ring_reduce == jnp.mean bitwise; plan-derived wire bytes
# ---------------------------------------------------------------------------

RING_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.training import grad_compress as GC

mesh = make_mesh((4,), ("pod",))
rng = np.random.default_rng(7)
# small-integer bf16 values: fp32 ring sums are exact in any hop order, so
# the mean is bitwise order-independent and comparable to jnp.mean
grads = {"w": jnp.asarray(rng.integers(-8, 8, size=(4, 128, 40)), jnp.bfloat16),
         "b": jnp.asarray(rng.integers(-8, 8, size=(4, 48)), jnp.bfloat16),
         "big": jnp.asarray(rng.integers(-4, 4, size=(4, 65536)), jnp.bfloat16)}
ref = jax.tree.map(lambda g: jnp.mean(g.astype(jnp.float32), axis=0)
                   .astype(g.dtype), grads)
cb = GC.calibrate_on_grads(jax.tree.map(lambda g: g[0], grads))
for kwargs in ({"compress": False}, {"codebook": cb}):
    out = GC.compressed_cross_pod_mean(grads, mesh, **kwargs)
    for k in ref:
        assert np.asarray(out[k]).tobytes() == np.asarray(ref[k]).tobytes(), k
s = GC.last_stats          # stats of the calibrated/compressed exchange
assert s is not None and s.wire_bytes > 0
# only 'big' clears MIN_COMPRESS_ELEMS per participant; it rode compressed
assert s.leaf_ok == {"big": True}, s.leaf_ok
print("RING-PARITY-OK")
"""


class TestGradRing:
    def test_ring_reduce_matches_mean_bitwise_subprocess(self):
        """Acceptance: compressed ring all-reduce over 4 pods equals the
        jnp.mean all-reduce bitwise (compressed AND raw routes), with
        TransferStats surfaced."""
        out = subprocess.run([sys.executable, "-c", RING_PARITY_SCRIPT],
                             capture_output=True, text=True,
                             env=_subprocess_env(), timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "RING-PARITY-OK" in out.stdout

    def test_cross_pod_wire_bytes_plan_derived(self):
        rng = np.random.default_rng(0)
        grads = {"w": jnp.asarray(rng.normal(size=(512, 64)), jnp.bfloat16),
                 "tiny": jnp.asarray(rng.normal(size=(8,)), jnp.bfloat16)}
        total = sum(g.size * 2 for g in jax.tree.leaves(grads))
        raw = GC.cross_pod_wire_bytes(grads, n_pod=3, compress=False)
        assert raw == pytest.approx(total * 2)          # 2 hops, no ratio
        est = GC.cross_pod_wire_bytes(grads, n_pod=3)
        # big leaf at the profile ratio, tiny leaf raw (route threshold)
        expected = (512 * 64 * 2 / PAPER_RATIO + 8 * 2) * 2
        assert est == pytest.approx(expected)
        assert est < raw
