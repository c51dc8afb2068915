"""Sharding rules (pure-logic on stub meshes + 1-device integration) and the
dry-run cell bookkeeping."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES_BY_NAME
from repro.configs.registry import get_config
from repro.sharding import rules
from repro.sharding.compression import (
    compressed_psum,
    dequantize_int8,
    init_error_feedback,
    quantize_int8,
)


def _stub_mesh(shape=(16, 16), axes=("data", "model")):
    m = types.SimpleNamespace()
    m.axis_names = axes
    m.devices = np.empty(shape, dtype=object)
    m.shape = dict(zip(axes, shape))
    return m


class TestParamSpecs:
    def test_attention_tp(self):
        axes = ("data", "model")
        cfg = get_config("minitron-8b")
        assert rules.param_spec("layers/b0/attn/wq", 2, cfg, axes) == P(("data",), "model")
        assert rules.param_spec("layers/b0/attn/wo", 2, cfg, axes) == P("model", ("data",))

    def test_fsdp_off_for_small(self):
        axes = ("data", "model")
        cfg = get_config("smollm-135m")  # fsdp=False
        assert rules.param_spec("layers/b0/attn/wq", 2, cfg, axes) == P(None, "model")

    def test_moe_expert_parallel(self):
        axes = ("data", "model")
        cfg = get_config("kimi-k2-1t-a32b")
        assert rules.param_spec("layers/moe/w_gate", 3, cfg, axes) == P("model", ("data",), None)
        assert rules.param_spec("layers/moe/w_down", 3, cfg, axes) == P("model", None, ("data",))
        assert rules.param_spec("layers/moe/router", 2, cfg, axes) == P(None, None)

    def test_embed_vocab_sharded(self):
        cfg = get_config("minitron-8b")
        assert rules.param_spec("embed", 2, cfg, ("data", "model")) == P("model", None)
        assert rules.param_spec("lm_head", 2, cfg, ("data", "model")) == P(None, "model")

    def test_norms_replicated(self):
        cfg = get_config("minitron-8b")
        assert rules.param_spec("layers/b0/attn_norm/scale", 1, cfg, ("data", "model")) == P(None)


class TestValidation:
    def test_indivisible_axis_dropped(self):
        mesh = _stub_mesh()
        spec = rules._validate_spec(P("model", None), (9, 4), mesh)  # 9 % 16 != 0
        assert spec == P(None, None)

    def test_divisible_kept(self):
        mesh = _stub_mesh()
        assert rules._validate_spec(P("model", None), (32, 4), mesh) == P("model", None)

    def test_tuple_axes_product(self):
        mesh = _stub_mesh((2, 16, 16), ("pod", "data", "model"))
        spec = rules._validate_spec(P(("pod", "data"), None), (64, 4), mesh)
        assert spec == P(("pod", "data"), None)
        spec = rules._validate_spec(P(("pod", "data"), None), (16, 4), mesh)  # 16 % 32
        assert spec == P(None, None)


class TestDataAndStateSpecs:
    def test_batch_sharded_over_dp(self):
        mesh = _stub_mesh()
        assert rules.data_spec((256, 4096), mesh) == P(("data",), None)

    def test_batch_one_replicated(self):
        mesh = _stub_mesh()
        assert rules.data_spec((1, 1), mesh) == P(None, None)

    def test_multipod_dp_axes(self):
        mesh = _stub_mesh((2, 16, 16), ("pod", "data", "model"))
        assert rules.data_spec((256, 4096), mesh) == P(("pod", "data"), None)

    def test_kv_cache_sequence_parallel(self):
        """Hkv=8 < model=16 → the 32k slot dim takes the model axis (SP)."""
        mesh = _stub_mesh()
        spec = rules.state_spec((32, 128, 8, 32768, 128), mesh, stacked=True)
        assert spec == P(None, ("data",), None, "model", None)

    def test_ssm_state_heads_sharded(self):
        mesh = _stub_mesh()
        spec = rules.state_spec((9, 1, 80, 64, 64), mesh, stacked=True)
        assert spec[2] == "model" or spec[3] == "model"


class TestCompression:
    def test_quantize_roundtrip_error(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (1024,))
        q, s = quantize_int8(x)
        err = float(jnp.max(jnp.abs(dequantize_int8(q, s) - x)))
        assert err <= float(s) * 0.5 + 1e-7

    def test_compressed_psum_single_axis(self):
        """On a 1-member axis the compressed psum must reproduce the gradient
        up to quantization error, and EF must hold the residual."""
        mesh = jax.make_mesh((1,), ("pod",))
        g = {"w": jax.random.normal(jax.random.PRNGKey(1), (64,))}
        ef = init_error_feedback(g)

        def f(gw, efw):
            out, new_ef = compressed_psum({"w": gw}, {"w": efw}, "pod")
            return out["w"], new_ef["w"]

        f_sh = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
        out, new_ef = f_sh(g["w"], ef["w"])
        scale = float(jnp.max(jnp.abs(g["w"]))) / 127.0
        assert float(jnp.max(jnp.abs(out - g["w"]))) <= scale * 0.5 + 1e-7
        np.testing.assert_allclose(
            np.asarray(out + new_ef), np.asarray(g["w"]), rtol=1e-5, atol=1e-6
        )


class TestDryrunBookkeeping:
    def test_skip_rules(self):
        from repro.launch.dryrun import cell_skip_reason

        long = SHAPES_BY_NAME["long_500k"]
        assert cell_skip_reason(get_config("minitron-8b"), long) is not None
        assert cell_skip_reason(get_config("gemma2-27b"), long) is not None
        assert cell_skip_reason(get_config("xlstm-1.3b"), long) is None
        assert cell_skip_reason(get_config("zamba2-2.7b"), long) is None
        assert cell_skip_reason(get_config("kimi-k2-1t-a32b"), SHAPES_BY_NAME["train_4k"]) is None

    def test_scan_groups(self):
        from repro.launch.dryrun import n_scan_groups

        assert n_scan_groups(get_config("minitron-8b")) == 32
        assert n_scan_groups(get_config("gemma2-27b")) == 23
        assert n_scan_groups(get_config("xlstm-1.3b")) == 6
        assert n_scan_groups(get_config("zamba2-2.7b")) == 9
        assert n_scan_groups(get_config("kimi-k2-1t-a32b")) == 60

    def test_collective_parser(self):
        from repro.launch.hlo_analysis import collective_bytes

        text = """
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %x), replica_groups={}
  %ag = bf16[512]{0} all-gather(bf16[256]{0} %y), dimensions={0}
  %nothing = f32[2]{0} add(f32[2]{0} %a, f32[2]{0} %b)
"""
        out = collective_bytes(text)
        assert out["all-reduce"] == 128 * 256 * 4
        assert out["all-gather"] == 512 * 2
        assert out["total"] == 128 * 256 * 4 + 1024

    def test_model_flops_positive(self):
        from repro.launch.flops import model_flops

        for arch in ("minitron-8b", "kimi-k2-1t-a32b", "xlstm-1.3b", "zamba2-2.7b"):
            cfg = get_config(arch)
            for s in ("train_4k", "prefill_32k", "decode_32k"):
                assert model_flops(cfg, SHAPES_BY_NAME[s]) > 0

    def test_moe_active_flops_much_smaller_than_total(self):
        from repro.launch.flops import _param_counts

        total, active = _param_counts(get_config("kimi-k2-1t-a32b"))
        assert total > 0.9e12  # ~1T total
        assert active < 0.05 * total  # 32B active
