"""Unit tests for sharding rules: TP baseline, FSDP (ZeRO-3), MoE dispatch
constraints, PD-disaggregated dp axes.  Uses an abstract 2x2(x2) mesh — no
compiles, just spec resolution."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import pytest  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.distributed.sharding import ShardingPolicy  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


@pytest.fixture(scope="module")
def mesh3():
    return make_mesh((2, 2, 2), ("pod", "data", "model"))


@pytest.fixture(scope="module")
def mesh2():
    return make_mesh((4, 2), ("data", "model"))


class TestFSDP:
    def test_params_gain_data_axis(self, mesh2):
        base = ShardingPolicy(mesh2)
        fsdp = ShardingPolicy(mesh2, fsdp=True)
        # FFN weight (D, F): TP on F; FSDP adds data on D
        s_base = base.spec_for_param("ffn/w_gate", (512, 2048))
        s_fsdp = fsdp.spec_for_param("ffn/w_gate", (512, 2048))
        assert s_base == P(None, "model")
        assert s_fsdp == P("data", "model")

    def test_scan_stacked_leading_dim_never_sharded(self, mesh2):
        fsdp = ShardingPolicy(mesh2, fsdp=True)
        s = fsdp.spec_for_param("layers/ffn/w_gate", (16, 512, 2048))
        assert s == P(None, "data", "model")

    def test_small_params_stay_replicated(self, mesh2):
        fsdp = ShardingPolicy(mesh2, fsdp=True)
        # norm scale of 8 elements: gathering costs more than it saves
        assert fsdp.spec_for_param("layers/norm1/scale", (16, 8)) == P(None, None)

    def test_indivisible_dims_not_sharded(self, mesh2):
        fsdp = ShardingPolicy(mesh2, fsdp=True)
        s = fsdp.spec_for_param("ffn/w_gate", (509, 2048))  # 509 prime
        assert s == P(None, "model")

    def test_expert_weights(self, mesh2):
        fsdp = ShardingPolicy(mesh2, fsdp=True)
        # (E, D, 2F): EP on E, FSDP picks the largest remaining dim
        s = fsdp.spec_for_param("layers/ffn/w_gate_up", (8, 128, 512, 1024))
        assert s == P(None, "model", None, "data")

    def test_opt_state_shards_like_params(self, mesh2):
        from repro.configs.base import get_config
        from repro.training import train_step as TS
        cfg = get_config("smollm-135m").reduced()
        fsdp = ShardingPolicy(mesh2, fsdp=True)
        st = TS.abstract_state(cfg)
        psh = fsdp.param_sharding(st.params)
        # m/v mirror params => FSDP applies to optimizer state for free
        flat_p = jax.tree.leaves(psh)
        assert any("data" in str(s.spec) for s in flat_p)


class TestMoEDispatchKinds:
    def test_disabled_by_default(self, mesh2):
        pol = ShardingPolicy(mesh2)
        assert pol.spec_for_activation("moe_td", (4096, 128)) is None
        assert pol.spec_for_activation("moe_te", (4096, 8)) is None

    def test_enabled(self, mesh2):
        pol = ShardingPolicy(mesh2, moe_dispatch_sharding=True)
        assert pol.spec_for_activation("moe_td", (4096, 128)) == P("data", None)
        assert pol.spec_for_activation("moe_te", (4096, 8)) == P("data", None)

    def test_indivisible_tokens_replicate(self, mesh2):
        pol = ShardingPolicy(mesh2, moe_dispatch_sharding=True)
        assert pol.spec_for_activation("moe_td", (4095, 128)) == P(None, None)

    def test_unknown_kind_raises(self, mesh2):
        pol = ShardingPolicy(mesh2, moe_dispatch_sharding=True)
        with pytest.raises(KeyError):
            pol.spec_for_activation("moe_ecd", (8, 64, 128))


class TestPDDisaggregation:
    def test_dp_axes_exclude_pod(self, mesh3):
        assert ShardingPolicy(mesh3).dp_axes() == ("pod", "data")
        assert ShardingPolicy(mesh3, pd_disaggregated=True).dp_axes() == \
            ("data",)

    def test_activation_batch_not_pod_sharded(self, mesh3):
        pol = ShardingPolicy(mesh3, pd_disaggregated=True)
        spec = pol.spec_for_activation("btd", (8, 128, 64))
        assert spec == P(("data",), None, None) or spec == P("data", None, None)


class TestFSDPTrainStepCompiles:
    def test_reduced_train_step_lowers_with_fsdp(self, mesh2):
        """End-to-end: FSDP train step lowers+compiles on the 4x2 mesh."""
        from repro.configs.base import ShapeConfig, get_config
        from repro.launch.dryrun import build_lowerable
        cfg = get_config("smollm-135m").reduced()
        shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
        pol = ShardingPolicy(mesh2, fsdp=True)
        jitted, args = build_lowerable(cfg, shape, pol)
        compiled = jitted.lower(*args).compile()
        assert compiled.cost_analysis() is not None
