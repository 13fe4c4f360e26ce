"""Sharding plans + a real (8-fake-device) mesh integration test."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import SHAPES, get_config
from repro.launch.sharding import make_rules, param_specs
from repro.models import build_model
from repro.models.sharding import shard, sharding_rules


class FakeMesh:
    """Shape-only stand-in so rule logic is testable without 256 devices."""

    def __init__(self, shape):
        self.shape = shape


def test_rules_divisibility_whisper():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = make_rules(get_config("whisper-small"), mesh,
                       SHAPES["prefill_32k"])
    assert "heads" not in rules          # 12 heads don't shard 16-way
    assert rules.get("d_ff") == "model"  # 3072 does
    assert rules.get("vocab") == "model"


def test_rules_experts_qwen():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = make_rules(get_config("qwen3-moe-235b-a22b"), mesh,
                       SHAPES["train_4k"])
    assert rules.get("experts") == "model"
    assert rules.get("heads") == "model"


def test_rules_batch_axes():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    r = make_rules(get_config("stablelm-12b"), mesh, SHAPES["train_4k"])
    assert tuple(r["batch"]) == ("pod", "data")
    r = make_rules(get_config("zamba2-7b"), mesh, SHAPES["long_500k"])
    assert "batch" not in r              # batch=1 can't shard
    assert tuple(r["kv_seq"]) == ("pod", "model")


@pytest.mark.parametrize("arch", ["stablelm-12b", "whisper-small",
                                  "qwen3-moe-235b-a22b", "zamba2-7b",
                                  "deepseek-v2-lite-16b", "xlstm-350m"])
def test_param_specs_always_divisible(arch):
    """Every sharded param dim must divide by its mesh extent."""
    cfg = get_config(arch)
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = make_rules(cfg, mesh, SHAPES["train_4k"])
    model = build_model(cfg)
    p_abs = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    specs = param_specs(p_abs, cfg, mesh, rules)
    flat_p = jax.tree_util.tree_leaves(p_abs)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert len(flat_p) == len(flat_s)
    n_sharded = 0
    for leaf, spec in zip(flat_p, flat_s):
        for dim, entry in zip(leaf.shape, tuple(spec)):
            if entry is None:
                continue
            n_sharded += 1
            ext = 1
            for a in ((entry,) if isinstance(entry, str) else entry):
                ext *= mesh.shape[a]
            assert dim % ext == 0, (arch, leaf.shape, spec)
    assert n_sharded > 0 or arch == "xlstm-350m"


def test_shard_noop_without_mesh():
    import jax.numpy as jnp
    x = jnp.ones((4, 8))
    assert shard(x, "batch", None) is x


def test_small_mesh_end_to_end():
    """Real lower+compile of a reduced arch on an 8-fake-device (2,4) mesh,
    in a subprocess so the forced device count can't leak into this one."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.configs import get_reduced_config, SHAPES
        from repro.configs.shapes import ShapeSuite
        from repro.launch.sharding import make_rules
        from repro.launch.steps import build_cell
        from repro.models.sharding import sharding_rules

        cfg = get_reduced_config("granite-3-2b", n_heads=8, n_kv_heads=4,
                                 head_dim=16, d_model=128, d_ff=256,
                                 vocab_size=512, vocab_pad_to=128)
        suite = ShapeSuite("t", "train", 64, 8)
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:8]).reshape(2, 4), ("data", "model"))
        rules = make_rules(cfg, mesh, suite)
        with mesh, sharding_rules(mesh, rules):
            fn, args, _ = build_cell(cfg, suite, mesh, rules=rules,
                                     ce_chunk=32)
            compiled = fn.lower(*args).compile()
        txt = compiled.as_text()
        print(json.dumps({
            "ok": True,
            "has_collective": ("all-reduce" in txt or
                                "all-gather" in txt or
                                "reduce-scatter" in txt),
        }))
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["ok"] and out["has_collective"]


def test_hlo_collective_parser():
    from repro.launch.hlo import collective_bytes
    text = (
        "%ar = f32[16,128]{1,0} all-reduce(f32[16,128]{1,0} %x), "
        "channel_id=1\n"
        "%ag = (bf16[4,8]{1,0}, bf16[4,8]{1,0}) all-gather(%a, %b)\n"
        "%cp = u32[2]{0} collective-permute(%c)\n"
        "%done = f32[1]{0} all-reduce-done(%ar)\n")
    out = collective_bytes(text)
    assert out["all-reduce"] == 16 * 128 * 4
    assert out["all-gather"] == 2 * 4 * 8 * 2
    assert out["collective-permute"] == 2 * 4
    assert out["total"] == sum(v for k, v in out.items() if k != "total")
