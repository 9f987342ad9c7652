"""Sharding rules unit tests + dry-run integration (subprocess, smoke
variant, so the 512-device override never leaks into this process)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import spec_for_axes, cache_axes_tree
from repro.launch.dryrun import collective_bytes, _shape_bytes

REPO = Path(__file__).resolve().parents[1]


def _fake_mesh(shape, names):
    """AbstractMesh stand-in: spec_for_axes only reads axis_names/shape."""
    import numpy as np
    devs = np.empty(shape, object)
    return type("M", (), {"axis_names": names,
                          "devices": type("D", (), {"shape": shape,
                                                    "size": devs.size})()})()


class TestSpecForAxes:
    def setup_method(self):
        self.multi = _fake_mesh((2, 16, 16), ("pod", "data", "model"))
        self.single = _fake_mesh((16, 16), ("data", "model"))

    def test_fsdp_tp_weight(self):
        # (embed, mlp) weight: FSDP over (pod,data), TP over model
        spec = spec_for_axes(("embed", "mlp"), (4096, 16384), self.multi)
        assert spec == P(("pod", "data"), "model")

    def test_divisibility_fixup_drops_axis(self):
        # kv dim 5*64=320 divides 16; 50 does not -> dropped
        assert spec_for_axes(("kv_qkv",), (320,), self.single) == P("model")
        assert spec_for_axes(("kv_qkv",), (50,), self.single) == P(None)

    def test_partial_fsdp_when_only_pod_divides(self):
        # dim 34 divides 2 (pod) but 34/2=17 doesn't divide 16 -> pod only
        spec = spec_for_axes(("embed",), (34,), self.multi)
        assert spec == P("pod")

    def test_no_duplicate_mesh_axis(self):
        # experts take 'model'; the expert-mlp dim must NOT reuse it
        spec = spec_for_axes(("experts", "embed", "mlp"),
                             (128, 4096, 1536), self.multi)
        assert spec == P("model", ("pod", "data"), None)

    def test_missing_axis_on_single_pod(self):
        spec = spec_for_axes(("embed",), (4096,), self.single)
        assert spec == P("data")

    def test_scalar(self):
        assert spec_for_axes((), (), self.single) == P()


class TestCacheAxes:
    def test_kv_cache_axes(self):
        cache = {"k": jax.ShapeDtypeStruct((2, 4, 64, 8, 16), jnp.bfloat16),
                 "v": jax.ShapeDtypeStruct((2, 4, 64, 8, 16), jnp.bfloat16),
                 "index": jax.ShapeDtypeStruct((), jnp.int32)}
        axes = cache_axes_tree(cache)
        assert axes["k"] == ("layers", "act_batch", "act_seq_model", None, None)
        assert axes["index"] == ()


class TestCollectiveParser:
    def test_shape_bytes(self):
        assert _shape_bytes("bf16[128,1024]{1,0}") == 128 * 1024 * 2
        assert _shape_bytes("(f32[8]{0}, f32[16]{0})") == 32 + 64
        assert _shape_bytes("u8[3]") == 3

    def test_collective_bytes(self):
        hlo = """
  %ag = bf16[64,256]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[1024]{0} all-reduce(%y), to_apply=%add
  %rs = f32[32]{0} reduce-scatter(%z)
  %a2a = bf16[16,16]{1,0} all-to-all(%w)
  %cp = f32[8]{0} collective-permute(%v)
  %agst = (f32[4]{0}, f32[4]{0}) all-gather-start(%q)
  %not-a-collective = f32[99]{0} add(%a, %b)
"""
        out = collective_bytes(hlo)
        assert out["all-gather"]["count"] == 2
        assert out["all-gather"]["bytes"] == 64 * 256 * 2 + 32
        assert out["all-reduce"]["bytes"] == 4096
        assert out["reduce-scatter"]["bytes"] == 128
        assert out["all-to-all"]["bytes"] == 512
        assert out["collective-permute"]["bytes"] == 32
        assert out["total_bytes"] == sum(
            out[c]["bytes"] for c in ("all-gather", "all-reduce",
                                      "reduce-scatter", "all-to-all",
                                      "collective-permute"))


@pytest.mark.slow
class TestDryRunIntegration:
    """End-to-end: the dry-run subprocess lowers+compiles smoke cells on the
    512-device multi-pod mesh."""

    @pytest.mark.parametrize("arch,shape", [
        ("phi3-medium-14b", "train_4k"),
        ("qwen3-moe-235b-a22b", "decode_32k"),
    ])
    def test_smoke_cell_compiles(self, tmp_path, arch, shape):
        out = tmp_path / "cell.json"
        env = dict(os.environ)
        env["PYTHONPATH"] = str(REPO / "src")
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
             "--shape", shape, "--mesh", "multi", "--variant", "smoke",
             "--out", str(out)],
            env=env, capture_output=True, text=True, timeout=420)
        assert r.returncode == 0, r.stderr[-2000:]
        rec = json.loads(out.read_text())
        assert rec["status"] == "ok"
        assert rec["devices"] == 512
        assert rec["cost_analysis"].get("flops", 0) > 0


class TestHostMesh:
    def test_axes_are_auto(self):
        """`with_sharding_constraint` and the chips placement need Auto axes
        (jax.make_mesh defaults to Explicit)."""
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh()
        assert mesh.axis_names == ("data",)
        assert mesh.axis_types == (jax.sharding.AxisType.Auto,)
        assert mesh.devices.size == len(jax.devices())

    def test_explicit_device_subset(self):
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(jax.devices()[:1])
        assert mesh.devices.size == 1
        assert mesh.devices.flat[0] == jax.devices()[0]
