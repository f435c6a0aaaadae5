"""Dry-run machinery on a small host mesh (8 devices): lower + compile +
memory/cost/collective extraction — the same code path as the production
512-chip run, at reduced scale. (Run via test_distributed_launcher.)"""
from __future__ import annotations

import jax
import pytest

from repro import configs
from repro.launch.dryrun import cost_analysis_dict, parse_collective_bytes
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_step
from repro.models.config import InputShape


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs ≥4 devices (run via test_distributed_launcher)")
    return make_host_mesh(model=2)


SHAPES = {
    "train": InputShape("t", seq_len=32, global_batch=8, kind="train"),
    "prefill": InputShape("p", seq_len=32, global_batch=8, kind="prefill"),
    "decode": InputShape("d", seq_len=64, global_batch=8, kind="decode"),
}


@pytest.mark.parametrize("arch_id", ["qwen2-0.5b", "olmoe-1b-7b", "mamba2-370m",
                                     "zamba2-2.7b", "seamless-m4t-large-v2",
                                     "internvl2-76b"])
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_lower_compile_small(mesh, arch_id, kind):
    cfg = configs.reduced_config(arch_id)
    shape = SHAPES[kind]
    with jax.set_mesh(mesh):
        bundle = build_step(cfg, shape, mesh)
        lowered = bundle.fn.lower(*bundle.arg_structs.values())
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes >= 0
    cost = cost_analysis_dict(compiled.cost_analysis())
    assert cost.get("flops", 0) > 0
    coll = parse_collective_bytes(compiled.as_text())
    # a sharded train/prefill step must communicate *something*
    if kind == "train":
        assert sum(v["bytes"] for v in coll.values()) > 0, coll


def test_collective_parser_units():
    txt = """
  %all-gather.1 = bf16[16,256]{1,0} all-gather(%p0), channel_id=1, replica_groups=[16,16]<=[256], dimensions={0}
  %all-reduce.2 = f32[128]{0} all-reduce(%x), channel_id=2, replica_groups=[2,128]<=[256], to_apply=%add
  %rs = f32[64]{0} reduce-scatter(%y), channel_id=3, replica_groups=[16,16]<=[256], dimensions={0}
  %cp = u32[2]{0} collective-permute(%z), channel_id=4, source_target_pairs={{0,1}}
  %not_a_collective = f32[4]{0} add(%a, %b)
"""
    got = parse_collective_bytes(txt)
    assert got["all-gather"]["count"] == 1
    assert got["all-gather"]["bytes"] == 16 * 256 * 2 * 15 // 16
    assert got["all-reduce"]["bytes"] == 2 * 128 * 4 * 127 // 128
    assert got["reduce-scatter"]["bytes"] == 64 * 4 * 15
    assert got["collective-permute"]["bytes"] == 8
    assert "add" not in got
