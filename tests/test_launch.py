"""Launch-layer tests: sharding rules, lowering machinery, HLO analysis.

These run on the single host device (mesh 1x1) with reduced configs — the
512-device production sweep is exercised by ``repro.launch.dryrun`` (see
EXPERIMENTS.md §Dry-run for the artifacts)."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeCfg
from repro.distributed import sharding as SH
from repro.launch.hlo_analysis import analyze_collectives, _shape_bytes
from repro.launch.mesh import make_host_mesh
from repro.models import api

TINY = ShapeCfg("tiny_train", seq_len=16, global_batch=2, kind="train")
TINY_DECODE = ShapeCfg("tiny_decode", seq_len=16, global_batch=2, kind="decode")


class TestShardingRules:
    def test_param_specs_cover_all_leaves(self):
        mesh = make_host_mesh(1, 1)
        for arch in ("smollm-360m", "grok-1-314b", "mamba2-130m",
                     "whisper-small", "recurrentgemma-9b"):
            cfg = get_config(arch).reduced()
            pspec = api.param_spec(cfg)
            specs = SH.params_pspecs_cfg(pspec, mesh, cfg)
            n_params = len(jax.tree_util.tree_leaves(pspec))
            n_specs = len(jax.tree_util.tree_leaves(
                specs, is_leaf=lambda x: isinstance(x, P)))
            assert n_specs == n_params

    def test_divisibility_fallbacks(self):
        """Dims that don't divide the axis must not be sharded."""
        mesh = make_host_mesh(1, 1)  # axes size 1: everything divisible
        cfg = get_config("smollm-360m").reduced()
        specs = SH.params_pspecs_cfg(api.param_spec(cfg), mesh, cfg)
        # with axis size 1 sharding is trivially valid; just check structure
        assert isinstance(jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))[0], P)

    def test_attn_modes(self):
        assert dataclasses.replace(get_config("mistral-large-123b"),
                                   tp_size=16).attn_mode == "head"
        assert dataclasses.replace(get_config("smollm-360m"),
                                   tp_size=16).attn_mode == "padded"
        assert dataclasses.replace(get_config("gemma-2b"),
                                   tp_size=16).attn_mode == "replicated"
        cfg = dataclasses.replace(get_config("arctic-480b"), tp_size=16)
        assert cfg.attn_mode == "padded" and cfg.padded_heads == 64
        # kv map: padded heads point at the last kv head
        assert cfg.kv_head_map()[-1] == cfg.n_kv_heads - 1

    def test_input_specs_shapes(self):
        for arch in ("internvl2-76b", "whisper-small", "mamba2-130m"):
            cfg = get_config(arch)
            for sname, shape in SHAPES.items():
                if not cfg.supports(shape):
                    continue
                specs = api.input_specs(cfg, shape)
                if shape.kind == "train":
                    assert specs["tokens"].shape == (shape.global_batch,
                                                     shape.seq_len)
                if shape.kind == "decode":
                    assert specs["token"].shape == (shape.global_batch,)
                    assert "caches" in specs


class TestLoweringOnHostMesh:
    @pytest.mark.parametrize("arch", ["smollm-360m", "mamba2-130m",
                                      "recurrentgemma-9b"])
    def test_train_step_lowers_and_compiles(self, arch):
        from repro.launch.dryrun import build_lowering
        mesh = make_host_mesh(1, 1)
        cfg = get_config(arch).reduced()
        lowered = build_lowering(cfg, TINY, mesh)
        compiled = lowered.compile()
        assert compiled.cost_analysis().get("flops", 0) > 0

    def test_decode_step_lowers_and_compiles(self):
        from repro.launch.dryrun import build_lowering
        mesh = make_host_mesh(1, 1)
        cfg = get_config("smollm-360m").reduced()
        compiled = build_lowering(cfg, TINY_DECODE, mesh).compile()
        assert compiled.memory_analysis().temp_size_in_bytes >= 0


class TestEntryPoints:
    @pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
    def test_compile_cache_dir(self, env_dir, monkeypatch):
        """``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set;
        without it the cache goes to one fixed, git-ignored checkout path."""
        from repro.launch import compile_cache
        updates = []
        monkeypatch.setattr(compile_cache.jax.config, "update",
                            lambda name, value: updates.append((name, value)))
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        got = compile_cache.enable_compile_cache()
        if env_dir is not None:
            assert got == env_dir and updates == []
            return
        root = Path(compile_cache.__file__).resolve().parents[3]
        assert got == str(root / ".jax_cache")
        assert updates == [("jax_compilation_cache_dir", got)]
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()

    def test_scenario_rejects_more_shards_than_devices(self):
        """The sharded scenario CLI refuses a mesh larger than the devices
        present instead of shrinking it."""
        import argparse
        from repro.launch.train import run_scenario
        n = len(jax.devices())
        args = argparse.Namespace(topology="multihop", seed=0, sim_dt=None,
                                  sim_shards=n + 1, sim_worker_shards=1,
                                  sim_dim=8, sim_impl="vectorized")
        with pytest.raises(SystemExit, match=f"needs {n + 1} devices"):
            run_scenario(args)


class TestHloAnalysis:
    def test_shape_bytes(self):
        assert _shape_bytes("f32[4,8]") == 128
        assert _shape_bytes("bf16[10]") == 20
        assert _shape_bytes("(f32[2,2], s32[3])") == 28

    def test_trip_count_weighting(self):
        """A collective inside a scanned body counts trip_count times."""
        mesh = make_host_mesh(1, 1)

        def f(x, ws):
            def body(c, w):
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return y.sum()

        with mesh:
            c = jax.jit(f).lower(
                jax.ShapeDtypeStruct((4, 8), jnp.float32),
                jax.ShapeDtypeStruct((5, 8, 8), jnp.float32)).compile()
        res = analyze_collectives(c.as_text())
        # single device: no collectives, but loop detection must find trip 5
        assert any(l["trip_count"] == 5 for l in res["loops"]) or \
            res["total_bytes"] == 0
