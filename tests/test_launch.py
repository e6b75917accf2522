"""Launch-layer unit tests: HLO collective parser, roofline terms, shape
cells — all pure shape/string math (no 512-device compiles here; those run in
scripts/sweep_dryrun.sh and the subprocess tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.launch.roofline import (
    CollectiveStats,
    model_flops,
    parse_collectives,
    peaks_for,
    roofline_terms,
    _type_bytes,
    _wire_bytes,
)
from repro.lm.shapes import SHAPES, cell_supported, input_specs
from repro.lm.steps import cache_struct

_HLO = """
  %ag = bf16[16,512,1024]{2,1,0} all-gather(bf16[1,512,1024]{2,1,0} %p0), replica_groups=[16,16]<=[256], dimensions={0}
  %ar.1 = f32[256,128]{1,0} all-reduce(f32[256,128]{1,0} %x), replica_groups={{0,1,2,3}}, to_apply=%add
  %rs = f32[64,128]{1,0} reduce-scatter(f32[1024,128]{1,0} %y), replica_groups=[32,16]<=[512], dimensions={0}
  %cp = u32[8]{0} collective-permute(u32[8]{0} %z), source_target_pairs={{0,1}}
  %a2a = bf16[4,4]{1,0} all-to-all(bf16[4,4]{1,0} %w), replica_groups={{0,1,2,3,4,5,6,7}}
  %done = f32[2] add(f32[2] %a, f32[2] %b)
"""


def test_type_bytes():
    assert _type_bytes("bf16[16,512,1024]{2,1,0}") == 16 * 512 * 1024 * 2
    assert _type_bytes("f32[256,128]{1,0}") == 256 * 128 * 4
    assert _type_bytes("(f32[2,2], bf16[4])") == 16 + 8
    assert _type_bytes("u32[8]{0}") == 32


def test_parse_collectives():
    st = parse_collectives(_HLO, total_devices=256)
    assert st.counts == {"all-gather": 1, "all-reduce": 1, "reduce-scatter": 1,
                         "collective-permute": 1, "all-to-all": 1}
    ag = 16 * 512 * 1024 * 2
    assert st.by_type["all-gather"] == pytest.approx(ag * 15 / 16)
    ar = 256 * 128 * 4
    assert st.by_type["all-reduce"] == pytest.approx(2 * ar * 3 / 4)  # group of 4
    rs = 64 * 128 * 4
    assert st.by_type["reduce-scatter"] == pytest.approx(rs * 15)     # group of 16
    assert st.by_type["collective-permute"] == 32.0


def test_parse_collectives_counts_each_channel_once():
    """The TPU compiler repeats an asynchronous all-gather inside each
    fusion that continues it, under the one channel_id: one collective."""
    ag = ("  %all-gather.{i} = f32[512,129,400]{{0,2,1}} all-gather("
          "%param_0.{i}), channel_id=360, replica_groups=[1,4]<=[4], "
          "dimensions={{0}}")
    text = "\n".join([ag.format(i=i) for i in range(15)] + [
        "  %all-reduce.1 = f32[8,128]{1,0} all-reduce(%x), channel_id=7, "
        "replica_groups={{0,1,2,3}}, to_apply=%add"])
    st = parse_collectives(text, total_devices=4)
    assert st.counts == {"all-gather": 1, "all-reduce": 1}
    big = 512 * 129 * 400 * 4
    assert st.payload_bytes == big + 8 * 128 * 4
    assert st.by_type["all-gather"] == pytest.approx(big * 3 / 4)
    assert st.largest_bytes == big
    # a combined all-reduce is many arrays: its largest is one of them
    st = parse_collectives(
        "  %ar = (f32[1600]{0}, f32[800,400]{1,0}) all-reduce(%a, %b), "
        "channel_id=9, replica_groups={{0,1,2,3}}", total_devices=4)
    assert st.largest_bytes == 800 * 400 * 4
    assert st.payload_bytes == (1600 + 800 * 400) * 4


def test_wire_bytes_factors():
    assert _wire_bytes("all-gather", 100, 1) == 0.0
    assert _wire_bytes("all-reduce", 100, 2) == pytest.approx(100.0)
    assert _wire_bytes("all-to-all", 160, 16) == pytest.approx(150.0)


def test_roofline_dominance():
    pk = peaks_for("TPU v5 lite")
    r = roofline_terms(pk.flops, pk.hbm_bw * 0.5, pk.ici_bw * 2,
                       "TPU v5 lite")
    assert r["compute_s"] == pytest.approx(1.0)
    assert r["memory_s"] == pytest.approx(0.5)
    assert r["collective_s"] == pytest.approx(2.0)
    assert r["dominant"] == "collective"
    assert r["roofline_fraction_compute"] == pytest.approx(0.5)


def test_roofline_peaks_by_device_kind():
    pk = peaks_for("TPU v5 lite")  # Google Cloud docs, "TPU v5e"
    assert (pk.flops, pk.hbm_bw, pk.ici_bw) == (197e12, 819e9, 50e9)
    with pytest.raises(ValueError, match="no published peaks"):
        peaks_for("TPU v4")
    with pytest.raises(ValueError):
        roofline_terms(1.0, 1.0, 1.0, "cpu")


def test_model_flops_train_vs_decode():
    cfg = ARCHS["qwen3-4b"]
    tr = model_flops(cfg, SHAPES["train_4k"], "train")
    dec = model_flops(cfg, SHAPES["decode_32k"], "decode")
    assert tr == pytest.approx(6 * cfg.param_count() * 256 * 4096, rel=1e-6)
    assert dec == pytest.approx(2 * cfg.param_count() * 128, rel=1e-6)
    moe = ARCHS["mixtral-8x22b"]
    assert moe.active_param_count() < moe.param_count()


def test_all_cells_have_specs():
    """input_specs must produce well-formed ShapeDtypeStructs for every
    runnable (arch x shape) cell — 40 cells, 7 documented skips."""
    runnable, skipped = 0, 0
    for name, cfg in ARCHS.items():
        for shape in SHAPES:
            if cell_supported(cfg, shape):
                skipped += 1
                continue
            runnable += 1
            specs = input_specs(cfg, shape)
            leaves = jax.tree.leaves(specs)
            assert leaves and all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)
            cell = SHAPES[shape]
            if cell.kind == "train":
                assert specs["batch"]["labels"].shape == (cell.global_batch,
                                                          cell.seq_len)
            elif cell.kind == "decode":
                assert specs["tokens"].shape == (cell.global_batch, 1)
    assert runnable == 33 and skipped == 7


def test_long_500k_skips_match_design():
    expected_skip = {"qwen2-72b", "qwen3-4b", "qwen2-0.5b", "internlm2-20b",
                     "grok-1-314b", "llava-next-34b", "whisper-large-v3"}
    actual = {n for n, c in ARCHS.items() if cell_supported(c, "long_500k")}
    assert actual == expected_skip
    # SSM / hybrid / SWA archs must run it
    for n in ("mamba2-1.3b", "jamba-v0.1-52b", "mixtral-8x22b"):
        assert cell_supported(ARCHS[n], "long_500k") is None


def test_cache_struct_shapes():
    cfg = ARCHS["mixtral-8x22b"]
    c = cache_struct(cfg, batch=4, s_cache=32768)
    k = c["pos0"]["k"]
    # SWA: the cache is the ring window, not the full sequence
    assert k.shape == (cfg.n_layers, 4, cfg.sliding_window, cfg.n_kv_heads,
                       cfg.resolved_head_dim)
    cfg2 = ARCHS["jamba-v0.1-52b"]
    c2 = cache_struct(cfg2, batch=2, s_cache=1024)
    assert "k" in c2["pos0"] and "ssm" in c2["pos1"]
    assert c2["pos1"]["ssm"].shape[0] == cfg2.n_layers // 8
