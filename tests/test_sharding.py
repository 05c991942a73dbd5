"""Sharding-rule unit tests: PartitionSpecs, layouts, abstract input specs."""

import jax
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch import sharding as shr
from repro.launch import steps as steps_mod
from repro.models.model import Model

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestParamSpecs:
    def test_wq_heads_sharded(self):
        spec = shr.param_pspec("stack/units/l0/attn/wq", (28, 4096, 32, 128), MESH, False)
        assert spec == P(None, ("data",), "model", None)

    def test_kv_heads_replicated_when_indivisible(self):
        # chatglm kv=2 < 16 -> model axis dropped
        spec = shr.param_pspec("stack/units/l0/attn/wk", (28, 4096, 2, 128), MESH, False)
        assert spec == P(None, ("data",), None, None)

    def test_kv_heads_sharded_when_divisible(self):
        spec = shr.param_pspec("stack/units/l0/attn/wk", (62, 5376, 16, 128), MESH, False)
        assert spec == P(None, ("data",), "model", None)

    def test_moe_expert_weights_ff_tp(self):
        spec = shr.param_pspec("stack/units/l0/ffn/w1", (56, 8, 6144, 16384), MESH, False)
        assert spec == P(None, None, ("data",), "model")

    def test_embedding_vocab_tp(self):
        spec = shr.param_pspec("embed/table", (65024, 4096), MESH, False)
        assert spec == P("model", ("data",))

    def test_multi_pod_fsdp_covers_pod(self):
        spec = shr.param_pspec("stack/units/l0/mlp/w1", (40, 2048, 8192), MESH_MP, True)
        assert spec == P(None, ("pod", "data"), "model")

    def test_norms_replicated(self):
        spec = shr.param_pspec("stack/units/l0/ln1/scale", (40, 2048), MESH, False)
        assert spec == P(None, None)

    def test_pure_dp_layout_has_no_tp(self):
        spec = shr.param_pspec(
            "stack/units/l0/attn/wq", (40, 2048, 32, 64), MESH, False, "pure_dp"
        )
        assert spec == P(None, ("data", "model"), None, None)
        spec = shr.param_pspec("embed/table", (49408, 2048), MESH, False, "pure_dp")
        assert spec == P(None, ("data", "model"))

    def test_ep_pod_layout_shards_experts_over_pod(self):
        spec = shr.param_pspec(
            "stack/units/l0/ffn/w1", (56, 8, 6144, 16384), MESH_MP, True, "ep_pod"
        )
        assert spec == P(None, "pod", ("data",), "model")
        # attention weights keep TP but FSDP drops to data-only
        spec = shr.param_pspec(
            "stack/units/l0/attn/wq", (56, 6144, 48, 128), MESH_MP, True, "ep_pod"
        )
        assert spec == P(None, ("data",), "model", None)


class TestCacheSpecs:
    def test_kv16_shards_heads(self):
        spec = shr.cache_pspec(
            "units/l0/k", (10, 128, 32768, 16, 128),
            configs.get_config("gemma3-27b"), MESH, False, 128,
        )
        assert spec == P(None, ("data",), None, "model", None)

    def test_kv8_shards_sequence(self):
        spec = shr.cache_pspec(
            "units/l0/k", (40, 128, 32768, 8, 128),
            configs.get_config("mistral-nemo-12b"), MESH, False, 128,
        )
        assert spec == P(None, ("data",), "model", None, None)

    def test_long_context_batch1_shards_seq_over_data(self):
        spec = shr.cache_pspec(
            "units/l0/k", (10, 1, 524288, 16, 128),
            configs.get_config("gemma3-27b"), MESH, False, 1,
        )
        assert spec == P(None, None, ("data",), "model", None)

    def test_ssm_state_heads_over_model(self):
        spec = shr.cache_pspec(
            "units/l1/ssm_state", (9, 128, 80, 64, 64),
            configs.get_config("zamba2-2.7b"), MESH, False, 128,
        )
        assert spec == P(None, ("data",), "model", None, None)


class TestActivationRules:
    def test_train_rules_sequence_parallel(self):
        cfg = configs.get_config("gemma3-27b")
        rules = shr.activation_rules(cfg, MESH, False, 32, mode="train", seq=4096)
        assert rules["act_btd"].spec == P(("data",), "model", None)
        assert rules["act_attn_in"].spec == P(("data",), None, None)
        assert rules["act_heads"].spec == P(("data",), None, "model", None)

    def test_decode_rules_no_sp(self):
        cfg = configs.get_config("gemma3-27b")
        rules = shr.activation_rules(cfg, MESH, False, 128, mode="decode", seq=32768)
        assert rules["act_btd"].spec == P(("data",), None, None)

    def test_batch1_replicated(self):
        cfg = configs.get_config("zamba2-2.7b")
        rules = shr.activation_rules(cfg, MESH, False, 1, mode="decode", seq=524288)
        assert rules["act_btd"].spec == P(None, None, None)


class TestVocabPadding:
    @pytest.mark.parametrize("arch", configs.all_arch_ids())
    def test_padded_vocab_shards_model_axis(self, arch):
        cfg = configs.get_config(arch)
        assert cfg.padded_vocab % 256 == 0
        assert cfg.padded_vocab >= cfg.vocab


class TestInputSpecs:
    @pytest.mark.parametrize("arch", configs.all_arch_ids())
    def test_train_specs_abstract(self, arch):
        cfg = configs.get_config(arch)
        model = Model(cfg)
        specs = steps_mod.input_specs(model, "train_4k")
        assert "state" in specs and "batch" in specs
        key = "frames" if cfg.family == "audio" else "tokens"
        assert specs["batch"][key].shape[:2] == (256, 4096)
        # ShapeDtypeStructs only — nothing allocated
        for leaf in jax.tree.leaves(specs):
            assert isinstance(leaf, jax.ShapeDtypeStruct)

    def test_decode_specs_have_cache_and_lengths(self):
        model = Model(configs.get_config("gemma3-27b"))
        specs = steps_mod.input_specs(model, "decode_32k")
        assert specs["batch"]["tokens"].shape == (128, 1)
        assert specs["lengths"].shape == (128,)
        # ring caches: local layers hold window=1024, globals the full 32k
        sizes = {
            leaf.shape[-3]
            for path, leaf in jax.tree_util.tree_leaves_with_path(specs["cache"])
            if path[-1].key in ("k", "v")
        }
        assert sizes == {1024, 32768}

    def test_cell_matrix_counts(self):
        """32 applicable cells + 8 documented skips (DESIGN.md §4)."""
        from repro.launch.dryrun import cell_applicable

        ok = skip = 0
        for arch in configs.all_arch_ids():
            cfg = configs.get_config(arch)
            for shape in shr.SHAPES:
                if cell_applicable(cfg, shape)[0]:
                    ok += 1
                else:
                    skip += 1
        assert ok == 32
        assert skip == 8
